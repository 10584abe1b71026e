"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: the production
mesh is built from 512 forced host devices.  ``main`` sets ``XLA_FLAGS``
(see :func:`force_host_devices`) before the first device query — jax locks
the device count when its backend starts, not at import.

Per cell:
1. REAL module (scan-over-layers, remat, microbatched) is lowered AND
   compiled — the pass/fail proof — and provides memory_analysis().
2. Roofline terms come from UNROLLED 1- and 2-superblock cost variants with
   n_micro=1 (XLA cost_analysis counts while bodies once, so a scanned module
   undercounts FLOPs/collectives by the trip count; the unrolled variants are
   exact and extrapolate linearly in depth and microbatch count — see
   EXPERIMENTS.md §Dry-run 'methodology').

Usage:
  python -m repro.launch.dryrun --arch mamba2-1.3b --shape decode_32k
  python -m repro.launch.dryrun --all --mesh both --out benchmarks/dryrun_results
"""
import argparse
import dataclasses
import json
import os
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import ARCH_IDS, ALIASES, get_config, cells, skipped_cells, resolve
from ..models.config import SHAPES, ModelConfig
from ..models import backbones as bb, sharding as shd
from ..models.backbones import superblock_layout
from ..algos.pg.ppo import make_lm_ppo_train_step
from ..train.optim import adam, OptState
from . import mesh as mesh_lib
from . import specs as specs_lib
from .hlo_analysis import collective_bytes, roofline_terms

F32 = jnp.float32
# the production meshes (launch/mesh.py) are TPU v5e pods
TARGET_DEVICE_KIND = "TPU v5 lite"

# gradient-accumulation microbatches per arch for train_4k (memory knob)
DEFAULT_MICRO = {
    "llama32_vision_90b": 16,
    "granite_34b": 8,
    "mixtral_8x7b": 8,
    "zamba2_7b": 4,
    "glm4_9b": 4,
    "qwen2_moe_a2p7b": 2,
    "gemma2_2b": 2,
    "phi3_mini_3p8b": 2,
    "mamba2_1p3b": 2,
    "whisper_medium": 2,
}

# archs whose TP-only bf16 weights exceed ~4 GB/chip: FSDP the serving path too
SERVE_FSDP = {"llama32_vision_90b", "granite_34b", "mixtral_8x7b"}


def _shardings(tree_specs, mesh):
    return jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), tree_specs)


def _batch_pspec(leaf, dp):
    if leaf.ndim == 0:
        return P()
    return P(dp, *([None] * (leaf.ndim - 1)))


# ---------------------------------------------------------------------------
# step builders (shared by the real module and the cost variants)
# ---------------------------------------------------------------------------

def build_train(cfg, aid, cell, mesh, *, n_micro, global_batch=None,
                unroll_micro=False):
    dp = shd.dp_axes()
    B = global_batch or cell.global_batch
    opt = adam(1e-4, grad_clip=1.0)
    p_specs = specs_lib.param_specs(cfg)
    p_pspecs = shd.param_pspecs(p_specs, cfg, fsdp_axes=dp)
    train_step = make_lm_ppo_train_step(
        cfg, opt, n_microbatches=n_micro, unroll_micro=unroll_micro,
        img_len=cfg.n_img_tokens if cfg.family == "vlm" else 0,
        enc_len=cfg.enc_len if cfg.family == "encdec" else 0,
        param_pspecs=p_pspecs)
    o_pspecs = OptState(step=P(), mu=p_pspecs, nu=p_pspecs)
    o_specs = OptState(
        step=jax.ShapeDtypeStruct((), jnp.int32),
        mu=jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, F32), p_specs),
        nu=jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, F32), p_specs))
    cell_b = dataclasses.replace(cell, global_batch=B)
    b_specs = specs_lib.train_batch_specs(cfg, cell_b)
    b_pspecs = jax.tree_util.tree_map(lambda l: _batch_pspec(l, dp), b_specs)

    jitted = jax.jit(
        train_step,
        in_shardings=(_shardings(p_pspecs, mesh), _shardings(o_pspecs, mesh),
                      _shardings(b_pspecs, mesh)),
        out_shardings=(_shardings(p_pspecs, mesh), _shardings(o_pspecs, mesh),
                       None),
        donate_argnums=(0, 1))
    return jitted, (p_specs, o_specs, b_specs)


def build_adam_only(cfg, mesh):
    """Optimizer-update-only step: subtracted from train variants so the
    microbatch extrapolation scales only the fwd/bwd part."""
    dp = shd.dp_axes()
    opt = adam(1e-4, grad_clip=1.0)

    def update_only(params, opt_state, grads):
        p2, o2, gn = opt.update(grads, opt_state, params)
        return p2, o2, gn

    p_specs = specs_lib.param_specs(cfg)
    p_pspecs = shd.param_pspecs(p_specs, cfg, fsdp_axes=dp)
    g_specs = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, F32), p_specs)
    o_pspecs = OptState(step=P(), mu=p_pspecs, nu=p_pspecs)
    o_specs = OptState(
        step=jax.ShapeDtypeStruct((), jnp.int32),
        mu=g_specs, nu=g_specs)
    jitted = jax.jit(
        update_only,
        in_shardings=(_shardings(p_pspecs, mesh), _shardings(o_pspecs, mesh),
                      _shardings(p_pspecs, mesh)),
        donate_argnums=(0, 1))
    return jitted, (p_specs, o_specs, g_specs)


def build_decode(cfg, aid, cell, mesh):
    dp = shd.dp_axes()
    fsdp = dp if aid in SERVE_FSDP else None

    def serve_step(params, cache, tokens):
        hidden, cache = bb.decode_step(params, cache, tokens, cfg)
        logits = bb.lm_logits(params, hidden, cfg)
        return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32), cache

    p_specs = specs_lib.param_specs(cfg)
    p_pspecs = shd.param_pspecs(p_specs, cfg, fsdp_axes=fsdp)
    c_specs = specs_lib.cache_specs(cfg, cell.global_batch, cell.seq_len)
    c_pspecs = bb.cache_pspecs(cfg, c_specs)
    B = cell.global_batch
    ndp = shd.n_batch_shards()
    tok_pspec = P(dp) if B % ndp == 0 and ndp > 1 else P()
    jitted = jax.jit(
        serve_step,
        in_shardings=(_shardings(p_pspecs, mesh), _shardings(c_pspecs, mesh),
                      NamedSharding(mesh, tok_pspec)),
        out_shardings=(NamedSharding(mesh, tok_pspec),
                       _shardings(c_pspecs, mesh)),
        donate_argnums=(1,))
    tok_specs = jax.ShapeDtypeStruct((B,), jnp.int32)
    return jitted, (p_specs, c_specs, tok_specs)


def build_prefill(cfg, aid, cell, mesh):
    dp = shd.dp_axes()
    fsdp = dp if aid in SERVE_FSDP else None

    def prefill_step(params, cache, tokens, *extra):
        kw = {}
        if cfg.family == "vlm":
            kw["img"] = extra[0]
        if cfg.family == "encdec":
            kw["enc_frames"] = extra[0]
        hidden, cache = bb.prefill(params, tokens, cfg, cache, **kw)
        logits = bb.lm_logits(params, hidden, cfg)
        return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32), cache

    p_specs = specs_lib.param_specs(cfg)
    p_pspecs = shd.param_pspecs(p_specs, cfg, fsdp_axes=fsdp)
    kw = specs_lib.prefill_specs(cfg, cell)
    c_specs, tok_specs = kw["cache"], kw["tokens"]
    c_pspecs = bb.cache_pspecs(cfg, c_specs)
    args = [tok_specs]
    arg_shardings = [NamedSharding(mesh, P(dp, None))]
    if "img" in kw:
        args.append(kw["img"])
        arg_shardings.append(NamedSharding(mesh, P(dp, None, None)))
    if "enc_frames" in kw:
        args.append(kw["enc_frames"])
        arg_shardings.append(NamedSharding(mesh, P(dp, None, None)))
    jitted = jax.jit(
        prefill_step,
        in_shardings=(_shardings(p_pspecs, mesh), _shardings(c_pspecs, mesh),
                      *arg_shardings),
        out_shardings=(NamedSharding(mesh, P(dp)),
                       _shardings(c_pspecs, mesh)),
        donate_argnums=(1,))
    return jitted, (p_specs, c_specs, *args)


# ---------------------------------------------------------------------------
# cost-variant machinery
# ---------------------------------------------------------------------------

def variant_layers(cfg: ModelConfig):
    """n_layers for the 1- and 2-superblock unrolled cost variants."""
    _, per, _ = superblock_layout(cfg)
    return per, 2 * per


def _variant_cfg(cfg: ModelConfig, n_layers: int) -> ModelConfig:
    kw = {"n_layers": n_layers, "unroll": True}
    if cfg.family == "encdec":
        kw["n_enc_layers"] = n_layers  # enc scales with dec in the variants
    return dataclasses.replace(cfg, **kw)


def measure(jitted, args) -> dict:
    """Lower+compile and return exact per-device cost terms (no loops)."""
    compiled = jitted.lower(*args).compile()
    cost_list = compiled.cost_analysis()
    cost = cost_list[0] if isinstance(cost_list, (list, tuple)) else cost_list
    coll = collective_bytes(compiled.as_text())
    out = {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": float(coll["total"]),
        "coll_by_kind": {k: coll[k] for k in
                         ("all-gather", "all-reduce", "reduce-scatter",
                          "all-to-all", "collective-permute")},
        "coll_counts": coll["counts"],
    }
    del compiled
    return out


def _combine(base: dict, delta: dict, n: float, tail: dict = None,
             n_tail: float = 0) -> dict:
    """base + n*delta (+ n_tail*tail) element-wise over cost terms."""
    def lin(key):
        v = base[key] + n * delta[key]
        if tail is not None:
            v += n_tail * tail[key]
        return v
    out = {k: lin(k) for k in ("flops", "bytes", "coll")}
    out["coll_by_kind"] = {
        k: base["coll_by_kind"][k] + n * delta["coll_by_kind"][k]
        + (n_tail * tail["coll_by_kind"][k] if tail else 0.0)
        for k in base["coll_by_kind"]}
    return out


def _sub(a: dict, b: dict) -> dict:
    return {
        "flops": a["flops"] - b["flops"],
        "bytes": a["bytes"] - b["bytes"],
        "coll": a["coll"] - b["coll"],
        "coll_by_kind": {k: a["coll_by_kind"][k] - b["coll_by_kind"][k]
                         for k in a["coll_by_kind"]},
    }


def _scale(a: dict, s: float) -> dict:
    return {
        "flops": a["flops"] * s,
        "bytes": a["bytes"] * s,
        "coll": a["coll"] * s,
        "coll_by_kind": {k: v * s for k, v in a["coll_by_kind"].items()},
    }


def _add(a: dict, b: dict) -> dict:
    return _sub(a, _scale(b, -1.0))


def cost_from_variants(cfg, aid, cell, mesh, n_micro) -> dict:
    """Exact roofline terms by depth/microbatch extrapolation."""
    n_sb, per, tail = superblock_layout(cfg)
    L1, L2 = variant_layers(cfg)
    cfg1, cfg2 = _variant_cfg(cfg, L1), _variant_cfg(cfg, L2)

    if cell.kind == "train":
        B_micro = max(cell.global_batch // n_micro, 1)
        m_adam1 = measure(*build_adam_only(cfg1, mesh))
        m1 = measure(*build_train(cfg1, aid, cell, mesh, n_micro=1,
                                  global_batch=B_micro, unroll_micro=True))
        m_adam2 = measure(*build_adam_only(cfg2, mesh))
        m2 = measure(*build_train(cfg2, aid, cell, mesh, n_micro=1,
                                  global_batch=B_micro, unroll_micro=True))
        f1, f2 = _sub(m1, m_adam1), _sub(m2, m_adam2)      # fwd/bwd only
        d = _sub(f2, f1)                                   # per-superblock
        # zamba2 tail: mamba-only layers ~ 1/attn_every of a superblock
        tail_d = _scale(d, 1.0 / cfg.attn_every) if tail else None
        per_micro = _combine(f1, d, n_sb - 1, tail=tail_d, n_tail=tail)
        full_adam = measure(*build_adam_only(cfg, mesh))
        return _add(_scale(per_micro, n_micro), full_adam)

    builder = build_prefill if cell.kind == "prefill" else build_decode
    m1 = measure(*builder(cfg1, aid, cell, mesh))
    m2 = measure(*builder(cfg2, aid, cell, mesh))
    d = _sub(m2, m1)
    tail_d = _scale(d, 1.0 / cfg.attn_every) if tail else None
    return _combine(m1, d, n_sb - 1, tail=tail_d, n_tail=tail)


# ---------------------------------------------------------------------------
# per-cell driver
# ---------------------------------------------------------------------------

def run_cell(arch: str, cell, *, multi_pod: bool, n_micro=None,
             save_dir=None, verbose=True, skip_variants=False,
             cfg_overrides=None, tag=""):
    aid = resolve(arch)
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    mesh_lib.install(mesh)
    n_micro = n_micro or DEFAULT_MICRO.get(aid, 2)

    # 1) REAL module: lower + compile (the pass/fail proof) + memory analysis
    t0 = time.time()
    if cell.kind == "train":
        jitted, args = build_train(cfg, aid, cell, mesh, n_micro=n_micro)
    elif cell.kind == "prefill":
        jitted, args = build_prefill(cfg, aid, cell, mesh)
    else:
        jitted, args = build_decode(cfg, aid, cell, mesh)
    lowered = jitted.lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    memory = {
        "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
        "output_bytes": getattr(mem, "output_size_in_bytes", None),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
    }
    del lowered, compiled

    n_chips = 512 if multi_pod else 256
    result = {
        "arch": aid, "shape": cell.name, "kind": cell.kind,
        "mesh": "2x16x16" if multi_pod else "16x16", "n_chips": n_chips,
        "n_micro": n_micro if cell.kind == "train" else None,
        "t_lower_s": round(t_lower, 1), "t_compile_s": round(t_compile, 1),
        "memory": memory,
        "n_params": cfg.n_params(), "n_active_params": cfg.n_active_params(),
    }

    # 2) cost variants -> roofline (single-pod table only)
    if not skip_variants:
        cost = cost_from_variants(cfg, aid, cell, mesh, n_micro)
        roof = roofline_terms({"flops": cost["flops"],
                               "bytes accessed": cost["bytes"]},
                              {"total": cost["coll"]}, n_chips,
                              device_kind=TARGET_DEVICE_KIND)
        tokens = cell.tokens if cell.kind != "decode" else cell.global_batch
        mult = 6 if cell.kind == "train" else 2
        model_flops = mult * cfg.n_active_params() * tokens
        total_hlo = roof["flops_per_device"] * n_chips
        result.update({
            "roofline": roof,
            "collectives_by_kind": cost["coll_by_kind"],
            "model_flops": model_flops,
            "useful_flops_ratio": model_flops / total_hlo if total_hlo else None,
        })

    if verbose:
        peak = (memory["peak_bytes"] or 0) / 2**30
        arg = (memory["argument_bytes"] or 0) / 2**30
        line = (f"[OK] {aid:22s} {cell.name:12s} mesh={result['mesh']:8s} "
                f"compile={t_compile:6.1f}s peak={peak:7.2f}GiB arg={arg:7.2f}GiB")
        if "roofline" in result:
            r = result["roofline"]
            line += (f" bottleneck={r['bottleneck']:10s} "
                     f"t=(c {r['t_compute_s']:.2e}|m {r['t_memory_s']:.2e}"
                     f"|n {r['t_collective_s']:.2e})s "
                     f"useful={result['useful_flops_ratio']:.2f}")
        print(line, flush=True)
    if save_dir:
        os.makedirs(save_dir, exist_ok=True)
        suffix = f"__{tag}" if tag else ""
        fn = f"{aid}__{cell.name}__{result['mesh']}{suffix}.json"
        with open(os.path.join(save_dir, fn), "w") as f:
            json.dump(result, f, indent=1)
    return result


def force_host_devices(n: int = 512) -> None:
    """Ask the CPU backend for ``n`` devices (the production mesh size).
    Must run before jax starts its backend; the process stays on the CPU."""
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    os.environ["JAX_PLATFORMS"] = "cpu"


def main():
    force_host_devices()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--micro", type=int, default=None)
    ap.add_argument("--out", default="benchmarks/dryrun_results")
    args = ap.parse_args()

    archs = list(ARCH_IDS) if (args.all or not args.arch) else [args.arch]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    n_ok = n_fail = n_skip = 0
    for arch in archs:
        for cell in SHAPES:
            if args.shape and cell.name != args.shape:
                continue
            if cell in skipped_cells(arch):
                print(f"[SKIP] {arch:22s} {cell.name:12s} "
                      f"(long-context inapplicable: full attention)", flush=True)
                n_skip += 1
                continue
            for mp in meshes:
                try:
                    run_cell(arch, cell, multi_pod=mp, n_micro=args.micro,
                             save_dir=args.out, skip_variants=mp)
                    n_ok += 1
                except Exception as e:
                    n_fail += 1
                    print(f"[FAIL] {arch} {cell.name} multi_pod={mp}: {e}",
                          flush=True)
                    traceback.print_exc()
    print(f"\ndry-run complete: {n_ok} ok, {n_fail} failed, {n_skip} skipped")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
