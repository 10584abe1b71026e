"""Sampler throughput (paper §2.1 / Fig 1 + the §3.2 SPS claim): steps/sec
for serial vs alternating sampling with batched action selection, scaling
with the env batch, and serial-fused vs sharded-fused TRAINING samples/sec
(paper §2.4 synchronous multi-GPU) on a forced 4-device CPU mesh.

The sharded rows run in a subprocess because XLA_FLAGS must be set before
jax initializes; results (all rows) are also written to
benchmarks/BENCH_samplers.json so the perf trajectory is tracked across
PRs."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax

from repro.envs import make_env
from repro.agents import make_categorical_pg_agent, make_dqn_agent
from repro.models.rl_models import make_pg_mlp, make_q_conv
from repro.samplers import SerialSampler, AlternatingSampler


def _time_sampler(sampler, params, state, iters=5):
    collect = jax.jit(sampler.collect)
    state, batch = collect(params, state)  # compile
    jax.block_until_ready(batch.reward)
    t0 = time.perf_counter()
    for _ in range(iters):
        state, batch = collect(params, state)
    jax.block_until_ready(batch.reward)
    dt = (time.perf_counter() - t0) / iters
    sps = sampler.n_envs * sampler.horizon / dt
    return dt * 1e6, sps


_SHARDED_BENCH = """
import os, time, jax
from repro.envs import make_env
from repro.agents import make_categorical_pg_agent
from repro.models.rl_models import make_pg_mlp
from repro.samplers import SerialSampler, ShardedSampler
from repro.algos import A2C
from repro.core.distributions import Categorical
from repro.runners import TrainLoop
from repro.runners.train_loop import split_keys
from repro.train.optim import adam
from repro.launch.mesh import make_data_mesh

N_ENVS, HORIZON, WINDOW = 128, 32, 10
env = make_env("cartpole")
model = make_pg_mlp(4, 2)
agent = make_categorical_pg_agent(model)
rng = jax.random.PRNGKey(0)
params = model.init(rng)

def time_loop(name, sampler, mesh):
    algo = A2C(model.apply, adam(1e-3), distribution=Categorical(2))
    loop = TrainLoop(sampler, algo, mesh=mesh)
    ts = algo.init_train_state(rng, params)
    ss = sampler.init(jax.random.PRNGKey(1))
    _, keys = split_keys(jax.random.PRNGKey(2), WINDOW)
    out = loop.run_window(ts, ss, None, keys)   # compile
    jax.block_until_ready(out[0].params)
    t0 = time.perf_counter()
    iters = 5
    for _ in range(iters):
        ts2, ss2, _, _, _ = loop.run_window(ts, ss, None, keys)
    jax.block_until_ready(ts2.params)
    dt = (time.perf_counter() - t0) / iters
    sps = N_ENVS * HORIZON * WINDOW / dt
    print(f"ROW,{name},{dt / WINDOW * 1e6:.1f},{sps:.0f}")

n_dev = jax.local_device_count()
mesh = make_data_mesh(n_dev)
time_loop("trainloop_serial_fused_a2c_B128",
          SerialSampler(env, agent, n_envs=N_ENVS, horizon=HORIZON), None)
time_loop(f"trainloop_sharded_fused_a2c_B128x{n_dev}dev",
          ShardedSampler(env, agent, n_envs=N_ENVS, horizon=HORIZON,
                         mesh=mesh), mesh)
"""


def _sharded_rows(n_devices: int = 0):
    """serial-fused vs sharded-fused training SPS, measured in a subprocess
    with forced host devices (XLA_FLAGS must precede jax init).  The mesh is
    sized to the physical cores (capped at 4): forcing more devices than
    cores benchmarks scheduler thrash, not data parallelism."""
    n_devices = n_devices or min(4, os.cpu_count() or 1)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["JAX_PLATFORMS"] = "cpu"  # forced host devices; the chip stays free
    env["PYTHONPATH"] = os.path.join(repo, "src")
    r = subprocess.run([sys.executable, "-c", _SHARDED_BENCH],
                       capture_output=True, text=True, env=env, timeout=900)
    if r.returncode != 0:
        raise RuntimeError(f"sharded bench failed:\n{r.stdout}\n{r.stderr}")
    rows = []
    for line in r.stdout.splitlines():
        if line.startswith("ROW,"):
            _, name, us, sps = line.split(",")
            rows.append({"name": name, "us_per_call": float(us),
                         "derived": f"{sps}_steps_per_sec"})
    return rows


def _write_json(rows, path=None):
    path = path or os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "BENCH_samplers.json")
    out = {}
    if os.path.exists(path):  # merge: bench_replay's tree_sample rows ride along
        with open(path) as f:
            out = json.load(f)
    out.update({r["name"]: {"us_per_call": r["us_per_call"],
                            "derived": r["derived"]} for r in rows})
    with open(path, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
        f.write("\n")


def run():
    rows = []
    rng = jax.random.PRNGKey(0)
    env = make_env("cartpole")
    model = make_pg_mlp(4, 2)
    agent = make_categorical_pg_agent(model)
    params = model.init(rng)
    for n_envs in (8, 32, 128):
        s = SerialSampler(env, agent, n_envs=n_envs, horizon=32)
        us, sps = _time_sampler(s, params, s.init(rng))
        rows.append({"name": f"serial_cartpole_B{n_envs}",
                     "us_per_call": round(us, 1),
                     "derived": f"{sps:.0f}_steps_per_sec"})
    s = AlternatingSampler(env, agent, n_envs=32, horizon=32)
    us, sps = _time_sampler(s, params, s.init(rng))
    rows.append({"name": "alternating_cartpole_B32",
                 "us_per_call": round(us, 1),
                 "derived": f"{sps:.0f}_steps_per_sec"})

    env = make_env("catch")
    qmodel = make_q_conv(1, 3, img_hw=(10, 5), channels=(16, 32),
                         kernels=(3, 3), strides=(1, 1), d_out=128)
    qagent = make_dqn_agent(qmodel, 3)
    qparams = qmodel.init(rng)
    s = SerialSampler(env, qagent, n_envs=32, horizon=16)
    st = s.init(rng, {"epsilon": 0.1})
    us, sps = _time_sampler(s, qparams, st)
    rows.append({"name": "serial_catch_vision_B32",
                 "us_per_call": round(us, 1),
                 "derived": f"{sps:.0f}_steps_per_sec"})

    rows.extend(_sharded_rows())
    _write_json(rows)
    return rows
