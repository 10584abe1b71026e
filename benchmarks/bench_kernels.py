"""Roofline decision gate for the Pallas kernels (beat-XLA-or-delete).

For every kernel x wired call-site this compares, on the TPU roofline
(launch/hlo_analysis.PEAKS for a TPU v5e):

- **baseline**: the pure-jnp reference math the call site would otherwise
  run, measured with XLA's own ``cost_analysis()`` (FLOPs + bytes accessed
  of the optimized HLO — works on CPU, and IS what the ``ref`` backend
  executes);
- **kernel**: an analytic block-traffic model of the Mosaic kernel — bytes
  from the BlockSpec fetch schedule (revolving buffers: a block whose index
  map is constant along a grid axis is fetched once across it), FLOPs from
  the tiles the kernel actually executes (causal/kv_len tile-skip counted).

Verdict per call-site: whichever side has the lower roofline time
``max(t_compute, t_memory)``.  A kernel must win EVERY wired call-site to
stay a ``pallas`` default under ``auto`` (kernels/registry.GATE_WINNERS);
losers are demoted to reference-only.  Results land in
``benchmarks/BENCH_kernels.json``; CPU wall-clock rows are informational
only (interpret-mode timings say nothing about Mosaic).

Train-path (fwd+bwd) accounting: the custom_vjp backward IS the reference
backward (recompute-from-residuals), so the kernel side of a grad call-site
is ``kernel_fwd + (baseline_grad - baseline_fwd)`` — only the forward
changes hands.
"""
from __future__ import annotations

import json
import math
import os
import time

import jax
import jax.numpy as jnp

from repro.launch.hlo_analysis import roofline_terms, xla_cost
from repro.kernels.flash_attention.ref import attention_reference
from repro.models import layers as L

F32 = jnp.float32
BYTES = 4  # gate accounting runs both sides in f32


def _roof(flops, byts):
    r = roofline_terms({"flops": flops, "bytes accessed": byts}, {"total": 0.0},
                       1, device_kind="TPU v5 lite")
    t = max(r["t_compute_s"], r["t_memory_s"])
    return t, ("compute" if r["t_compute_s"] >= r["t_memory_s"] else "memory")


def _case(name, base_cost, kern_flops, kern_bytes):
    tb, _ = _roof(base_cost["flops"], base_cost["bytes accessed"])
    tk, bk = _roof(kern_flops, kern_bytes)
    return {
        "baseline": {"flops": base_cost["flops"],
                     "bytes": base_cost["bytes accessed"],
                     "t_roofline_s": tb},
        "kernel": {"flops": kern_flops, "bytes": kern_bytes,
                   "t_roofline_s": tk, "bottleneck": bk},
        "speedup": tb / max(tk, 1e-30),
        "verdict": "kernel" if tk < tb else "xla",
        "name": name,
    }


# ---------------------------------------------------------------------------
# analytic kernel cost models (mirror the BlockSpecs in kernels/*)
# ---------------------------------------------------------------------------

def attn_kernel_model(B, T, S, H, dh, *, causal, bq=128, bk=128):
    """Grid (B, H, T/bq, S/bk), KV innermost.  q/o fetched once per
    (b,h,iq); k,v re-streamed per q block (their index map changes every ik
    step).  FLOPs only on executed tiles (causal skip)."""
    bq, bk = min(bq, T), min(bk, S)
    nq, nk = T // bq, S // bk
    byts = BYTES * B * H * (2 * T * dh + nq * S * dh * 2)
    tiles = 0
    for iq in range(nq):
        if causal:
            tiles += min(nk, math.ceil(((iq + 1) * bq) / bk))
        else:
            tiles += nk
    flops = B * H * tiles * (4 * bq * bk * dh + 10 * bq * bk)
    return flops, byts


def ssd_kernel_model(B, T, H, P, G, N, *, Q=64, bh=8):
    """Grid (B, H/bh, T/Q), chunk innermost; state lives in VMEM scratch."""
    bh = min(bh, H // G)
    while (H // G) % bh:
        bh -= 1
    n_tiles = B * (H // bh) * (T // Q)
    byts = BYTES * (n_tiles * (2 * Q * bh * P + Q * bh + bh + 2 * Q * N)
                    + B * H * P * N)
    per_tile = (Q * Q * (5 * bh + 2 * N + 2 * bh * P)
                + Q * bh * P * (4 * N + 4))
    return n_tiles * per_tile, byts


def sumtree_kernel_model(size, batch, *, bs=512, block_b=256):
    """Grid (batch/block_b,); the whole priority table's index map is
    constant, so leaves+block_sums stream in once."""
    bs = min(bs, size)
    n_blocks = size // bs
    block_b = min(block_b, batch)
    steps = batch // block_b
    byts = BYTES * (size + n_blocks + 3 * batch)
    flops = steps * (n_blocks + 2 * block_b * n_blocks + 3 * block_b * bs)
    return flops, byts


# ---------------------------------------------------------------------------
# call-sites
# ---------------------------------------------------------------------------

def _attention_cases():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    cases = []

    # LM-scale PPO train step (launch/train.py via attention_train):
    # fwd + bwd; only the forward changes hands under the custom_vjp.
    B, T, H, Hkv, dh = 4, 1024, 8, 4, 64
    q = jax.random.normal(ks[0], (B, T, H, dh), F32)
    k = jax.random.normal(ks[1], (B, T, Hkv, dh), F32)
    v = jax.random.normal(ks[2], (B, T, Hkv, dh), F32)
    ref = lambda q, k, v: attention_reference(q, k, v, causal=True)
    c_fwd = xla_cost(ref, q, k, v)
    c_grad = xla_cost(jax.grad(lambda q, k, v: ref(q, k, v).sum(),
                               argnums=(0, 1, 2)), q, k, v)
    kf, kb = attn_kernel_model(B, T, T, H, dh, causal=True)
    cases.append(_case(f"attention/ppo_train_fwd_B{B}xT{T}", c_fwd, kf, kb))
    cases.append(_case(
        f"attention/ppo_train_grad_B{B}xT{T}", c_grad,
        kf + (c_grad["flops"] - c_fwd["flops"]),
        kb + (c_grad["bytes accessed"] - c_fwd["bytes accessed"])))

    # serve.py decode: one query token vs a (B, S) KV cache with kv_len.
    B, S = 8, 2048
    qd = jax.random.normal(ks[0], (B, 1, H, dh), F32)
    kc = jax.random.normal(ks[1], (B, S, Hkv, dh), F32)
    vc = jax.random.normal(ks[2], (B, S, Hkv, dh), F32)
    kvl = jnp.full((B,), S // 2, jnp.int32)
    c_dec = xla_cost(lambda q, k, v, l: attention_reference(
        q, k, v, causal=False, kv_len=l), qd, kc, vc, kvl)
    kf, kb = attn_kernel_model(B, 1, S, H, dh, causal=False, bq=1)
    cases.append(_case(f"attention/serve_decode_B{B}xS{S}", c_dec, kf, kb))
    return cases


def _ssd_cases():
    B, T, H, P, G, N, Q = 4, 1024, 16, 64, 2, 64, 64
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    x = jax.random.normal(ks[0], (B, T, H, P), F32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, H), F32))
    A = -jnp.exp(jnp.linspace(0.0, 2.0, H))
    Bm = jax.random.normal(ks[2], (B, T, G, N), F32)
    Cm = jax.random.normal(ks[3], (B, T, G, N), F32)
    ref = lambda x, dt, Bm, Cm: L.ssd_chunked(x, dt, A, Bm, Cm, Q)[0]
    c_fwd = xla_cost(ref, x, dt, Bm, Cm)
    c_grad = xla_cost(jax.grad(lambda x, dt, Bm, Cm: ref(x, dt, Bm, Cm).sum(),
                               argnums=(0, 1, 2, 3)), x, dt, Bm, Cm)
    kf, kb = ssd_kernel_model(B, T, H, P, G, N, Q=Q)
    return [
        _case(f"ssd/mamba2_train_fwd_B{B}xT{T}", c_fwd, kf, kb),
        _case(f"ssd/mamba2_train_grad_B{B}xT{T}", c_grad,
              kf + (c_grad["flops"] - c_fwd["flops"]),
              kb + (c_grad["bytes accessed"] - c_fwd["bytes accessed"])),
    ]


def _sumtree_cases():
    from repro.replay import device as dreplay
    from repro.kernels import registry

    cases = []
    size, batch = 2**17, 256
    pr = jax.random.uniform(jax.random.PRNGKey(2), (size,)) + 0.01
    with registry.override("ref"):
        tree = dreplay.tree_set(jnp.zeros((2 * size,), F32),
                                jnp.arange(size), pr)
    k = jax.random.PRNGKey(3)

    with registry.override("ref"):
        c_desc = xla_cost(lambda t, k: dreplay.tree_sample(t, k, batch)[0],
                          tree, k)
    kf, kb = sumtree_kernel_model(size, batch)
    cases.append(_case(f"sum_tree/replay_sample_{size}x{batch}", c_desc, kf, kb))

    # tree_set: both sides are jnp programs (the blocked rebuild is the
    # kernel-layout companion, not a Pallas body) — XLA cost on each.
    idx = jnp.arange(batch, dtype=jnp.int32) * 7 % size
    upd = jax.random.uniform(k, (batch,))
    # fresh lambdas per backend: jit caches on the function OBJECT, so
    # tracing the same `tree_set` twice would reuse the first backend's trace
    with registry.override("ref"):
        c_walk = xla_cost(lambda t, i, u: dreplay.tree_set(t, i, u),
                          tree, idx, upd)
    with registry.override("interpret"):
        c_blk = xla_cost(lambda t, i, u: dreplay.tree_set(t, i, u),
                         tree, idx, upd)
    cases.append(_case(f"sum_tree/replay_update_{size}x{batch}", c_walk,
                       c_blk["flops"], c_blk["bytes accessed"]))
    return cases


# ---------------------------------------------------------------------------
# informational CPU wall-clock (jnp-vs-jnp only; interpret timings excluded)
# ---------------------------------------------------------------------------

def _timeit(fn, iters=3):
    out = fn()
    jax.tree_util.tree_leaves(out)[0].block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    jax.tree_util.tree_leaves(out)[0].block_until_ready()
    return (time.perf_counter() - t0) / iters * 1e6


def _wall_rows():
    from repro.replay import device as dreplay
    from repro.kernels import registry

    rows = []
    size, batch = 2**17, 256
    pr = jax.random.uniform(jax.random.PRNGKey(2), (size,)) + 0.01
    with registry.override("ref"):
        tree = dreplay.tree_set(jnp.zeros((2 * size,), F32),
                                jnp.arange(size), pr)
    k = jax.random.PRNGKey(3)
    for spec, kind in (("ref", "descent"), ("interpret", "blocked")):
        with registry.override(spec):
            f = jax.jit(lambda t, k: dreplay.tree_sample(t, k, batch)[0])
            us = _timeit(lambda: f(tree, k))
        rows.append({"name": f"kernels_wall_tree_sample_{kind}_{size}",
                     "us_per_call": round(us, 1), "derived": "cpu_wall"})
    return rows


def _write_json(cases, gate, path=None):
    path = path or os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "BENCH_kernels.json")
    out = {c["name"]: {kk: c[kk] for kk in
                       ("baseline", "kernel", "speedup", "verdict")}
           for c in cases}
    out["gate"] = gate
    with open(path, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
        f.write("\n")


def run():
    cases = _attention_cases() + _ssd_cases() + _sumtree_cases()
    gate = {}
    for op in ("attention", "ssd", "sum_tree"):
        mine = [c for c in cases if c["name"].startswith(op + "/")]
        won = all(c["verdict"] == "kernel" for c in mine)
        gate[op] = "pallas-default" if won else "demoted-to-ref"
    rows = []
    for c in cases:
        rows.append({"name": "kernels_" + c["name"].replace("/", "_"),
                     "us_per_call": round(c["baseline"]["t_roofline_s"] * 1e6, 3),
                     "derived": f"{c['speedup']:.2f}x_{c['verdict']}"})
    rows.extend(_wall_rows())
    _write_json(cases, gate)
    return rows
