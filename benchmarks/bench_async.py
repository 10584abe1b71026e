"""Decoupled async runner vs synchronous fused TrainLoop (paper §2.3 vs
§2.4): DQN training samples/sec at low (k=1) and high (k=8)
updates_per_collect.

The synchronous loop pays all k update times inside the sampling critical
path — SPS = S / (c + k*u) — while the async actor free-runs and the
learner consumes under the replay-ratio throttle (an UPPER bound, rlpyt
§2.3), so in the update-dominated regime async sampling throughput is
higher.  The flip side is reported honestly in the derived column: the
achieved replay ratio (rr) can fall below the target when the learner is
compute-bound, and parameters go stale.  rc is the steady-state recompile
count (must be 0 on both programs); ov is the measured actor/learner busy
overlap fraction.

The bench runs in a subprocess so XLA_FLAGS can force one host device per
physical core (capped at 4); with >1 device the sync comparator is the
sharded-fused TrainLoop on a data mesh, otherwise the serial-fused loop
(the same one-program composite on a single device).  All rows
merge-write to benchmarks/BENCH_async.json.

``python benchmarks/bench_async.py --smoke`` runs a short threaded run
in-process and asserts nonzero throughput, measured overlap > 0, and zero
steady-state recompiles — the CI async smoke step.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

_ASYNC_BENCH = """
import os, time
import numpy as np
import jax

from repro.envs import make_env
from repro.agents import make_dqn_agent
from repro.models.rl_models import make_q_mlp
from repro.samplers import SerialSampler, ShardedSampler
from repro.algos import DQN
from repro.runners import AsyncRunner
from repro.runners.train_loop import TrainLoop, split_keys
from repro.replay.interface import DeviceReplay, transition_example
from repro.replay.host import UniformReplayBuffer, TransitionSamples
from repro.train.optim import adam
from repro.launch.mesh import make_data_mesh
from repro.utils.logger import Logger

N_ENVS, HORIZON, BATCH, WINDOW = 16, 16, 256, 8
MIN_REPLAY, CAPACITY = 1024, 8192
N_MEAS = 40                      # measured iterations (second, warm run)
EPS = {"epsilon": 0.1}

env = make_env("cartpole")
# wide hidden layers put the bench in the update-dominated regime: one
# batch-256 update costs more than one 16-env rollout step
model = make_q_mlp(4, 2, hidden=(256, 256))
agent = make_dqn_agent(model, 2)
rng = jax.random.PRNGKey(0)
params = model.init(rng)


def sync_row(k):
    n_dev = jax.local_device_count()
    mesh = make_data_mesh(n_dev) if n_dev > 1 else None
    if mesh is not None:
        sampler = ShardedSampler(env, agent, n_envs=N_ENVS, horizon=HORIZON,
                                 mesh=mesh)
        tag = f"sharded_fused_{n_dev}dev"
    else:
        sampler = SerialSampler(env, agent, n_envs=N_ENVS, horizon=HORIZON)
        tag = "serial_fused"
    algo = DQN(model.apply, adam(1e-3), double=True)
    replay = DeviceReplay(CAPACITY)
    loop = TrainLoop(sampler, algo, replay=replay, batch_size=BATCH,
                     updates_per_collect=k, fuse=True, mesh=mesh)
    ts = algo.init_train_state(rng, params)
    ss = sampler.init(jax.random.PRNGKey(1), EPS)
    ex = transition_example(env)
    rs = (replay.init_sharded(ex, loop.n_shards) if mesh is not None
          else replay.init(ex))
    warm = 0
    while warm < MIN_REPLAY:
        ss, rs = loop.collect_insert(params, ss, rs)
        warm += N_ENVS * HORIZON
    keys = split_keys(jax.random.PRNGKey(2), WINDOW)[1]
    out = loop.run_window(ts, ss, rs, keys)   # compile
    jax.block_until_ready(out[0].params)
    t0 = time.perf_counter()
    iters = max(1, N_MEAS // WINDOW)
    for _ in range(iters):
        out = loop.run_window(ts, ss, rs, keys)
    jax.block_until_ready(out[0].params)
    dt = (time.perf_counter() - t0) / iters
    sps = N_ENVS * HORIZON * WINDOW / dt
    print(f"ROW,sync_{tag}_dqn_k{k},{dt / WINDOW * 1e6:.1f},"
          f"{sps:.0f}sps_rr{k * BATCH / (N_ENVS * HORIZON):.2f}_ov0.00_rc0")


def async_row(k):
    sampler = SerialSampler(env, agent, n_envs=N_ENVS, horizon=HORIZON)
    algo = DQN(model.apply, adam(1e-3), double=True)
    ex = TransitionSamples(observation=np.zeros(4, np.float32),
                           action=np.int32(0), reward=np.float32(0),
                           done=False, timeout=False)
    buf = UniformReplayBuffer(ex, T_size=CAPACITY // N_ENVS, B=N_ENVS,
                              n_step=1)
    target = k * BATCH / (N_ENVS * HORIZON)
    runner = AsyncRunner(sampler, algo, buf, batch_size=BATCH,
                         replay_ratio=target, min_replay=MIN_REPLAY,
                         n_iterations=N_MEAS, log_interval=N_MEAS,
                         threaded=True, publish_interval=1,
                         agent_state_kwargs=EPS,
                         logger=Logger(stream=open(os.devnull, "w"),
                                       sinks=("console",)))
    runner.run(jax.random.PRNGKey(3))            # compile + warm buffer
    runner.run(jax.random.PRNGKey(4))            # measured, steady state
    s = runner.stats
    us = s["elapsed_s"] / N_MEAS * 1e6
    print(f"ROW,async_threaded_dqn_k{k},{us:.1f},"
          f"{s['samples_per_sec']:.0f}sps_rr{s['replay_ratio_actual']:.2f}"
          f"_ov{s['overlap_frac']:.2f}_rc{s['recompile_events']}")


for k in (1, 8):
    sync_row(k)
    async_row(k)
"""


def _bench_rows(n_devices: int = 0):
    n_devices = n_devices or min(4, os.cpu_count() or 1)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["JAX_PLATFORMS"] = "cpu"  # forced host devices; the chip stays free
    env["PYTHONPATH"] = os.path.join(repo, "src")
    r = subprocess.run([sys.executable, "-c", _ASYNC_BENCH],
                       capture_output=True, text=True, env=env, timeout=900)
    if r.returncode != 0:
        raise RuntimeError(f"async bench failed:\n{r.stdout}\n{r.stderr}")
    rows = []
    for line in r.stdout.splitlines():
        if line.startswith("ROW,"):
            _, name, us, derived = line.split(",")
            rows.append({"name": name, "us_per_call": float(us),
                         "derived": derived})
    return rows


def _write_json(rows, path=None):
    path = path or os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "BENCH_async.json")
    out = {}
    if os.path.exists(path):
        with open(path) as f:
            out = json.load(f)
    out.update({r["name"]: {"us_per_call": r["us_per_call"],
                            "derived": r["derived"]} for r in rows})
    with open(path, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
        f.write("\n")


def run():
    rows = _bench_rows()
    _write_json(rows)
    return rows


def smoke():
    """CI async smoke: a short threaded DQN run must deliver nonzero
    throughput with measured actor/learner overlap and zero steady-state
    recompiles on both compiled programs."""
    import numpy as np
    import jax

    from repro.envs import make_env
    from repro.agents import make_dqn_agent
    from repro.models.rl_models import make_q_mlp
    from repro.samplers import SerialSampler
    from repro.algos import DQN
    from repro.runners import AsyncRunner
    from repro.replay.host import UniformReplayBuffer, TransitionSamples
    from repro.train.optim import adam
    from repro.utils.logger import Logger

    env = make_env("cartpole")
    model = make_q_mlp(4, 2)
    agent = make_dqn_agent(model, 2)
    algo = DQN(model.apply, adam(1e-3), double=True)
    sampler = SerialSampler(env, agent, n_envs=8, horizon=16)
    ex = TransitionSamples(observation=np.zeros(4, np.float32),
                           action=np.int32(0), reward=np.float32(0),
                           done=False, timeout=False)
    buf = UniformReplayBuffer(ex, T_size=128, B=8, n_step=1)
    runner = AsyncRunner(sampler, algo, buf, batch_size=64, replay_ratio=1.0,
                         min_replay=128, n_iterations=16, log_interval=4,
                         threaded=True, publish_interval=2,
                         agent_state_kwargs={"epsilon": 0.3},
                         logger=Logger(stream=open(os.devnull, "w"),
                                       sinks=("console",)))
    runner.run(jax.random.PRNGKey(0))   # compile + fill the buffer
    runner.run(jax.random.PRNGKey(1))   # steady state: assert on this run
    s = runner.stats
    assert s["samples_per_sec"] > 0, s
    assert s["overlap_frac"] > 0, s
    assert s["recompile_events"] == 0, s
    assert s["updates"] > 0, s
    print(f"async smoke ok: {s['samples_per_sec']:.0f} samples/sec, "
          f"overlap {s['overlap_frac']:.2f}, "
          f"replay_ratio {s['replay_ratio_actual']:.2f}, "
          f"recompile_events {s['recompile_events']}")


if __name__ == "__main__":
    if "--smoke" in sys.argv:
        smoke()
    else:
        for r in run():
            print(f"{r['name']},{r['us_per_call']},{r['derived']}")
