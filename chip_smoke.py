#!/usr/bin/env python3
"""Bring-up check: the main path runs on a TPU through its normal entry points.

    python chip_smoke.py              # one chip: phases a-e
    python chip_smoke.py --chips 4    # four-chip host: the multi-chip paths only

One process holds the chip for the whole run.  Each phase prints one line to
stdout; what the programs themselves print goes to stderr, and their logs to
``logs/chip_smoke/``.  The last line of a passing run is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
A failed phase is reported and the script exits non-zero without that line.
There is no CPU fallback: without a TPU the script exits 2 at phase a.

One chip:
  a. device: a TPU, and every kernel op resolving to its compiled backend;
  b. kernels: each Pallas kernel against its jnp reference at real widths;
  c. the scan-fused RL loop (paper §2.2): prioritized double-dueling DQN on
     Catch through DeviceReplay (the sum-tree kernel), PPO on CartPole and
     SAC on Pendulum, through OffPolicyRunner / OnPolicyRunner;
  d. the LM-PPO trainer (``launch/train.py``) on mamba2-1.3b at published
     widths, depth cut to fit one chip, with a fused window of 2 steps;
  e. serving (``launch/serve.py --full --continuous``): all 48 layers of
     mamba2-1.3b through the continuous-batching engine.
Four chips (``--chips 4``):
  the sharded fused A2C TrainLoop over a 4-chip data mesh against the
  global-batch update on one chip (paper §2.4), and LM-PPO on ``--mesh 1x4``
  and ``--mesh 2x2`` against ``1x1`` with parameters spread over the chips.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import math
import os
import shutil
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

# the program lives in src/: imported once the path holds it
import jax
import jax.numpy as jnp
import numpy as np

from repro.agents import (make_categorical_pg_agent, make_dqn_agent,
                          make_sac_agent)
from repro.algos import A2C, DQN, PPO, SAC
from repro.configs import get_config
from repro.core.distributions import Categorical
from repro.envs import make_env
from repro.kernels import registry
from repro.kernels.flash_attention.ops import (
    flash_attention, flash_attention_decode)
from repro.kernels.flash_attention.ref import attention_reference
from repro.kernels.ssd_scan.ops import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_reference
from repro.kernels.sum_tree.ops import (
    tree_sample_blocked, tree_update_blocked)
from repro.launch import serve, train
from repro.launch.mesh import make_data_mesh
from repro.models.rl_models import (make_pg_mlp, make_q_conv,
                                    make_q_critic, make_sac_actor)
from repro.runners import OffPolicyRunner, OnPolicyRunner, TrainLoop
from repro.runners.train_loop import split_keys
from repro.samplers import SerialSampler, ShardedSampler
from repro.telemetry import trace
from repro.train.optim import adam
from repro.utils.compile_cache import enable_compile_cache

LOG_DIR = os.path.join(REPO, "logs", "chip_smoke")

# Kernel outputs must satisfy |kernel - reference| <= KERNEL_TOL *
# max(1, max|reference|).  2e-2 is the bound tests/test_kernels.py holds bf16
# inputs to: one bf16 rounding of the output is 2^-8 relative, and the
# chip's default f32 matmul runs in bf16 passes.  References run at
# "highest" matmul precision.
KERNEL_TOL = 2e-2

LM_ARCH = "mamba2-1.3b"
# Whole mamba2-1.3b layers whose LM-PPO fused window (batch 8, horizon 64,
# params + Adam donated) fits one 16 GB v5e.  The chip's compiler counts
# 10.27 GB at 16 layers, 14.06 GB at 24 and runs out of memory at 32
# (memory_analysis() of the window compiled for a described v5e); 16 keeps
# room for the eager init and the rollout cache beside the program.
LM_TRAIN_LAYERS = 16
# The four-chip comparison compiles three LM programs (one per mesh) with
# the layer stack unrolled; 4 layers keep their compile within the run.
LM_MESH_LAYERS = 4
# LM-PPO metrics on a mesh against 1x1: the runs sample different tokens
# (see phase_lm_meshes), so this is a sanity bound, not a parity one.
MESH_RTOL = 0.1


class _Rows:
    """A logger that keeps the rows the runners record."""

    def __init__(self):
        self.rows = []

    def record(self, step, row):
        self.rows.append(dict(row, step=step))


def _finite(x) -> bool:
    return math.isfinite(float(x))


def _fresh_dir(*parts) -> str:
    path = os.path.join(LOG_DIR, *parts)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


def _read_jsonl(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _events(kind):
    return [e for e in trace.get_tracer().events if e["kind"] == kind]


# -- a. device ---------------------------------------------------------------

def check_device(n_chips: int):
    """The first device must be a TPU and the host must hold ``n_chips``;
    otherwise exit 2 here, before any phase runs."""

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX's first device is {devices[0].platform!r}",
              file=sys.stderr)
        sys.exit(2)
    if len(devices) < n_chips:
        print(f"{n_chips} chips asked for, JAX sees {len(devices)}",
              file=sys.stderr)
        sys.exit(2)
    return devices


def phase_device(devices, cache_dir):

    backends = registry.describe()
    bad = {op: be for op, be in backends.items()
           if be == "interpret" or (be == "ref" and op in registry.GATE_WINNERS)}
    if bad:
        raise RuntimeError(f"kernel ops not on their compiled backend: {bad}")
    n_cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    return (f"{devices[0].device_kind} x{len(devices)}; kernels {backends}; "
            f"compile cache {n_cached} entries at start")


# -- b. kernels against their references ----------------------------------------

def _attention_cases(key, *, T=2048, S=4096, B_dec=8):
    """(name, kernel thunk, reference thunk) at the tested widths: glm4-9b
    heads (H=32, Hkv=2, dh=128) and mixtral-8x7b heads (Hkv=8, window
    4096), training at T and one decode step against an S-slot cache."""

    def normal(k, shape):
        return jax.random.normal(k, shape, jnp.bfloat16)

    cases = []
    for name, hkv, window in (("glm4", 2, None), ("mixtral", 8, 4096)):
        kq, kk, kv, kd, kl, key = jax.random.split(key, 6)
        q = normal(kq, (1, T, 32, 128))
        k, v = normal(kk, (1, T, hkv, 128)), normal(kv, (1, T, hkv, 128))
        cases.append((
            f"attention_train_{name}",
            lambda q=q, k=k, v=v, w=window: flash_attention(
                q, k, v, causal=True, window=w, interpret=False),
            lambda q=q, k=k, v=v, w=window: attention_reference(
                q, k, v, causal=True, window=w)))
        qd = normal(kd, (B_dec, 1, 32, 128))
        kc, vc = normal(kk, (B_dec, S, hkv, 128)), normal(kv, (B_dec, S, hkv, 128))
        kv_len = jax.random.randint(kl, (B_dec,), 1, S + 1)
        cases.append((
            f"attention_decode_{name}",
            lambda q=qd, k=kc, v=vc, n=kv_len: flash_attention_decode(
                q, k, v, n, interpret=False),
            lambda q=qd, k=kc, v=vc, n=kv_len: attention_reference(
                q, k, v, causal=False, kv_len=n)))
    return cases


def _ssd_case(key, *, T=2048, H=64, P=64, N=128, chunk=256):
    """mamba2-1.3b's SSD widths: H=64 heads of P=64, state N=128, G=1."""

    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (1, T, H, P), jnp.float32) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, T, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (1, T, 1, N)) * 0.3
    Cm = jax.random.normal(ks[4], (1, T, 1, N)) * 0.3
    return ("ssd_mamba2",
            lambda: ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=False),
            lambda: ssd_reference(x, dt, A, Bm, Cm, chunk=chunk))


def _check_sum_tree(key, *, capacity=2 ** 20, batch=256):
    """Sample the blocked kernel over a DeviceReplay tree of ``capacity``
    leaves.  Priorities are small integers, so every prefix sum is exact in
    float32 and the float64 oracle's index must match exactly."""

    kp, ku = jax.random.split(key)
    prio = jax.random.randint(kp, (capacity,), 1, 9).astype(jnp.float32)
    tree = tree_update_blocked(jnp.zeros((2 * capacity,), jnp.float32),
                               jnp.arange(capacity), prio)
    total = float(tree[1])
    u = (jnp.arange(batch) + jax.random.uniform(ku, (batch,))) / batch * total
    idx, prob = tree_sample_blocked(tree, u, interpret=False)
    cum = np.cumsum(np.asarray(prio, np.float64))
    want = np.minimum(np.searchsorted(cum, np.asarray(u, np.float64),
                                      side="right"), capacity - 1)
    idx = np.asarray(idx)
    n_wrong = int(np.sum(idx != want))
    prob_err = float(np.max(np.abs(
        np.asarray(prob, np.float64) - np.asarray(prio)[want] / cum[-1])))
    if n_wrong or prob_err > 1e-6 * float(np.max(np.asarray(prob))):
        raise AssertionError(f"sum_tree: {n_wrong}/{batch} indices differ "
                             f"from the float64 oracle, prob err {prob_err:.3g}")
    return f"sum_tree 0/{batch} wrong, prob err {prob_err:.2g}"


def phase_kernels(key=None, *, attention=None, ssd=None, sum_tree=None):

    key = jax.random.PRNGKey(0) if key is None else key
    k_attn, k_ssd, k_tree = jax.random.split(key, 3)
    cases = _attention_cases(k_attn, **(attention or {}))
    cases.append(_ssd_case(k_ssd, **(ssd or {})))
    report, failed = [], []
    for name, kernel, reference in cases:
        got = jax.tree_util.tree_leaves(jax.block_until_ready(kernel()))
        with jax.default_matmul_precision("highest"):
            want = jax.tree_util.tree_leaves(reference())
        for i, (g, w) in enumerate(zip(got, want)):
            g = np.asarray(g.astype(jnp.float32))
            w = np.asarray(w.astype(jnp.float32))
            err = float(np.max(np.abs(g - w)))
            bound = KERNEL_TOL * max(1.0, float(np.max(np.abs(w))))
            tag = name if len(got) == 1 else f"{name}[{i}]"
            report.append(f"{tag} {err:.3g}")
            if not np.isfinite(g).all() or err > bound:
                failed.append(f"{tag}: max abs err {err:.3g} > {bound:.3g}")
    report.append(_check_sum_tree(k_tree, **(sum_tree or {})))
    if failed:
        raise AssertionError("; ".join(failed))
    return "max abs err: " + ", ".join(report)


# -- c. the fused RL loop ----------------------------------------------------------

def _check_rows(name, rows, n_windows):
    if len(rows) != n_windows:
        raise AssertionError(f"{name}: {len(rows)} log rows, want {n_windows}")
    for r in rows:
        bad = {k: r.get(k) for k in ("sent_nonfinite_grads",
                                     "sent_nonfinite_params") if r.get(k)}
        if not _finite(r["loss"]) or bad:
            raise AssertionError(f"{name}: non-finite at step {r['step']}: "
                                 f"loss {r['loss']} {bad}")
    return f"{name} loss {float(rows[-1]['loss']):.4g}"


def run_dqn(iters=30, log_interval=10, replay_capacity=8192):
    """Prioritized double-dueling DQN on Catch (examples/catch_dqn_variants,
    'dueling'), through the fused device-replay runner."""

    trace.configure(None)
    model = make_q_conv(1, 3, img_hw=(10, 5), channels=(16, 32),
                        kernels=(3, 3), strides=(1, 1), d_out=128,
                        dueling=True)
    algo = DQN(model.apply, adam(5e-4), gamma=0.99, double=True,
               target_update_interval=100)
    sampler = SerialSampler(make_env("catch"), make_dqn_agent(model, 3),
                            n_envs=16, horizon=16)
    rows = _Rows()
    OffPolicyRunner(sampler, algo, replay_capacity=replay_capacity,
                    batch_size=64, n_iterations=iters, updates_per_collect=2,
                    min_replay=512, prioritized=True,
                    log_interval=log_interval, logger=rows, sentinels=True,
                    agent_state_kwargs={"epsilon": 0.2}).run(
        jax.random.PRNGKey(0))
    tree_backends = {e["backend"] for e in _events("kernel_dispatch")
                     if e["op"] == "sum_tree"}
    line = _check_rows("dqn", rows.rows, iters // log_interval)
    return f"{line} (sum_tree {sorted(tree_backends)})", tree_backends


def run_ppo(iters=30, log_interval=10):
    """PPO on CartPole (examples/quickstart)."""

    model = make_pg_mlp(obs_dim=4, n_actions=2)
    algo = PPO(model.apply, adam(7e-4, grad_clip=0.5),
               distribution=Categorical(2), epochs=4, minibatches=4)
    sampler = SerialSampler(make_env("cartpole"),
                            make_categorical_pg_agent(model),
                            n_envs=16, horizon=64)
    rows = _Rows()
    OnPolicyRunner(sampler, algo, n_iterations=iters,
                   log_interval=log_interval, logger=rows,
                   sentinels=True).run(jax.random.PRNGKey(0))
    return _check_rows("ppo", rows.rows, iters // log_interval)


def run_sac(iters=30, log_interval=10):
    """SAC on Pendulum through the fused device-replay runner
    (tests/test_learning.py's configuration)."""

    actor = make_sac_actor(3, 1, hidden=(64, 64))
    critic = make_q_critic(3, 1, hidden=(64, 64))
    algo = SAC(actor.apply, critic.apply, adam(1e-3), adam(1e-3), act_dim=1,
               init_alpha=0.2)
    sampler = SerialSampler(make_env("pendulum"), make_sac_agent(actor, 1),
                            n_envs=8, horizon=32)
    k = jax.random.PRNGKey(0)
    params = {"actor": actor.init(k), "critic": critic.init(k)}
    rows = _Rows()
    OffPolicyRunner(sampler, algo, replay_capacity=16384, batch_size=128,
                    n_iterations=iters, updates_per_collect=32,
                    min_replay=1024, log_interval=log_interval, logger=rows,
                    sentinels=True).run(k, params=params)
    return _check_rows("sac", rows.rows, iters // log_interval)


def phase_rl_loop(**sizes):
    dqn, tree_backends = run_dqn(**sizes.get("dqn", {}))
    if tree_backends != {"pallas"}:
        raise AssertionError(f"DeviceReplay's sum tree ran on "
                             f"{sorted(tree_backends)}, not the kernel")
    return "; ".join([dqn, run_ppo(**sizes.get("ppo", {})),
                      run_sac(**sizes.get("sac", {}))])


# -- d. LM-PPO trainer --------------------------------------------------------------

def run_lm_ppo(cfg, tag, argv):
    """``launch/train.py`` with ``cfg`` in place of the CLI's config; returns
    (final params, logged rows)."""

    log_dir = _fresh_dir(tag)
    args = train.parse_args(["--arch", cfg.name, "--log-dir", log_dir] + argv)
    params = train.run(args, cfg)
    rows = _read_jsonl(os.path.join(log_dir, "progress.jsonl"))
    for r in rows:
        if not all(_finite(r[k]) for k in ("loss", "entropy", "avg_reward")):
            raise AssertionError(f"{tag}: non-finite metrics {r}")
    return params, rows


def lm_config(n_layers, cfg=None):
    cfg = cfg or get_config(LM_ARCH)
    return dataclasses.replace(cfg, n_layers=n_layers)


def phase_lm_ppo(cfg=None, *, steps=4, batch=8, horizon=64, window=2):
    cfg = cfg or lm_config(LM_TRAIN_LAYERS)
    _, rows = run_lm_ppo(cfg, "lm_ppo", [
        "--steps", str(steps), "--batch", str(batch),
        "--horizon", str(horizon), "--fuse-window", str(window)])
    if len(rows) != steps // window:
        raise AssertionError(f"lm_ppo: {len(rows)} windows, want "
                             f"{steps // window}")
    compiles = sum(e["n_new"] for e in _events("recompile")
                   if e["name"] == "lm.fused_window")
    if compiles != 1:
        raise AssertionError(f"lm_ppo: fused window compiled {compiles}x")
    ssd = {e["backend"] for e in _events("kernel_dispatch")
           if e["op"] == "ssd"}
    last = rows[-1]
    return (f"{cfg.name} {cfg.n_layers}/48 layers d_model {cfg.d_model}: "
            f"{len(rows)} windows of {window}, loss {last['loss']:.5g}, "
            f"entropy {last['entropy']:.5g}, "
            f"{last['samples_per_sec']:.4g} samples/s (last window), "
            f"ssd {sorted(ssd)}")


# -- e. serving -------------------------------------------------------------------

def phase_serve(argv=None, n_requests=8):

    argv = argv or ["--arch", LM_ARCH, "--full"]
    summary = serve.main(argv + [
        "--continuous", "--requests", str(n_requests), "--batch", "8",
        "--prompt-len", "32", "--gen", "32", "--gen-min", "8", "--rate", "16",
        "--log-dir", _fresh_dir("serve")])
    if (summary["n_finished"] != n_requests or summary["n_rejected"]
            or summary["recompile_events"] or not summary["generated_tokens"]):
        raise AssertionError(f"serve: {summary}")
    return (f"{n_requests}/{n_requests} requests, "
            f"{summary['generated_tokens']} tokens, "
            f"{summary['decode_tok_per_sec']:.4g} decode tok/s, "
            f"p50 latency {summary['p50_latency_s']:.3g}s, "
            f"recompiles {summary['recompile_events']}")


# -- four chips ---------------------------------------------------------------------

def phase_sharded_a2c(n_chips=4, iters=20):
    """The shard_map'd fused A2C window on a data mesh equals the unsharded
    TrainLoop on the full batch (tests/test_sharded_train.py, on chips).
    Both run at "highest" matmul precision so only the sharding differs."""

    mesh = make_data_mesh(n_chips)
    env, model = make_env("cartpole"), make_pg_mlp(4, 2)
    agent = make_categorical_pg_agent(model)
    rng = jax.random.PRNGKey(0)
    params = model.init(rng)
    algo = A2C(model.apply, adam(1e-3), distribution=Categorical(2))

    def run(loop_mesh):
        sampler = ShardedSampler(env, agent, n_envs=8 * n_chips, horizon=16,
                                 mesh=mesh)
        loop = TrainLoop(sampler, algo, mesh=loop_mesh)
        ts = algo.init_train_state(rng, params)
        ss = sampler.init(jax.random.PRNGKey(1))
        _, keys = split_keys(jax.random.PRNGKey(2), iters)
        with jax.default_matmul_precision("highest"):
            ts, _, _, infos, _ = loop.run_window(ts, ss, None, keys)
        return ts, infos

    ts_ref, infos_ref = run(None)
    ts_sh, infos_sh = run(mesh)
    err = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
              for a, b in zip(jax.tree_util.tree_leaves(ts_ref.params),
                              jax.tree_util.tree_leaves(ts_sh.params)))
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-4),
        ts_ref.params, ts_sh.params)
    np.testing.assert_allclose(np.asarray(infos_ref.loss),
                               np.asarray(infos_sh.loss), atol=1e-4,
                               rtol=1e-4)
    return (f"sharded A2C over {n_chips} chips == global batch after "
            f"{iters} updates (max param diff {err:.3g})")


def _memory(stat):
    """``stat`` of every local device's memory_stats(), in bytes."""
    return [d.memory_stats()[stat] for d in jax.local_devices()]


def phase_lm_meshes(cfg=None, *, steps=2, batch=8, horizon=32):
    """LM-PPO on --mesh 1x4 and 2x2 against 1x1, compression off.  Model
    sharding reorders bf16 reductions, so sampled tokens diverge from 1x1
    within the first rollout and the runs agree only statistically: each
    metric must be finite and within MESH_RTOL of 1x1.  Each sharded run
    must spread its parameters evenly over the chips, and no chip may
    peak above the others (device 0 holds no whole copy at init)."""

    cfg = cfg or lm_config(LM_MESH_LAYERS)
    argv = ["--steps", str(steps), "--batch", str(batch), "--horizon",
            str(horizon), "--fuse-window", str(steps)]
    out, parts = {}, []
    for spec in ("1x4", "2x2", "1x1"):
        before = _memory("bytes_in_use")
        params, rows = run_lm_ppo(cfg, f"lm_mesh_{spec}",
                                  argv + ["--mesh", spec])
        held = [a - b for a, b in zip(_memory("bytes_in_use"), before)]
        # peaks only grow: 1x4 then 2x2 (more per chip) run before 1x1
        peak = _memory("peak_bytes_in_use")
        del params
        gc.collect()
        out[spec] = rows[-1]
        mib = lambda xs: [round(x / 2 ** 20) for x in xs]
        parts.append(f"{spec}: loss {rows[-1]['loss']:.6g}, params "
                     f"MiB/chip {mib(held)}, peak MiB/chip {mib(peak)}")
        if spec == "1x1":
            continue
        for name, xs in (("parameters", held), ("peak", peak)):
            if max(xs) > 1.25 * (sum(xs) / len(xs)):
                raise AssertionError(f"{spec}: {name} not spread: "
                                     f"{mib(xs)} MiB")
    for spec in ("1x4", "2x2"):
        for key in ("loss", "entropy", "avg_reward"):
            got, want = out[spec][key], out["1x1"][key]
            if abs(got - want) > MESH_RTOL * abs(want):
                raise AssertionError(f"{spec} {key} {got} vs 1x1 {want}")
    return f"{cfg.n_layers}-layer {cfg.name}: " + "; ".join(parts)


# -- main ----------------------------------------------------------------------------

def run_phases(phases) -> bool:
    """Run each (name, thunk); one stdout line per phase.  Returns whether
    all passed.  Programs' own output goes to stderr."""
    ok = True
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            with contextlib.redirect_stdout(sys.stderr):
                detail = fn()
        except Exception:
            traceback.print_exc()
            ok = False
            print(f"[{name}] FAILED after {time.perf_counter() - t0:.1f}s",
                  flush=True)
            continue
        print(f"[{name}] ok in {time.perf_counter() - t0:.1f}s: {detail}",
              flush=True)
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the multi-chip paths, on a four-chip host")
    args = ap.parse_args(argv)


    cache_dir = enable_compile_cache()
    devices = check_device(args.chips)
    if args.chips == 4:
        phases = [("a2c_data_mesh", phase_sharded_a2c),
                  ("lm_ppo_meshes", phase_lm_meshes)]
    else:
        phases = [("a_device", lambda: phase_device(devices, cache_dir)),
                  ("b_kernels", phase_kernels),
                  ("c_rl_loop", phase_rl_loop),
                  ("d_lm_ppo", phase_lm_ppo),
                  ("e_serve", phase_serve)]
    if not run_phases(phases):
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
