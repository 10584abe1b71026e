"""Learning-curve benches (paper Figs 4-6 at CPU scale): one short run per
algorithm family; curves land in benchmarks/curves/*.csv, the CSV row
reports final average return.  Budgets are deliberately small — these are
the exercise-every-algorithm benches, not score chasing.

Also benches the TrainLoop dispatch modes: samples/sec with log_interval
iterations fused into one lax.scan program vs. one jitted dispatch per
iteration (``dispatch_fused_*`` / ``dispatch_periter_*`` rows).Also benches the 2-D (data x model) LM-PPO train path (launch/train.py
--mesh): fused-window samples/sec at 1x1 vs 2x2, compression off/on, plus
the int8 error-feedback all-reduce payload accounting
(``trainloop_2d_*`` rows, merge-written into BENCH_samplers.json)."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp

from repro.envs import make_env
from repro.agents import (make_categorical_pg_agent, make_dqn_agent,
                          make_sac_agent, make_ddpg_agent)
from repro.algos import PPO, A2C, DQN, SAC, TD3, DDPG
from repro.core.distributions import Categorical
from repro.models.rl_models import (make_pg_mlp, make_q_conv, make_sac_actor,
                                    make_ddpg_actor, make_q_critic)
from repro.replay.interface import DeviceReplay, transition_example
from repro.samplers import SerialSampler
from repro.runners import OnPolicyRunner, OffPolicyRunner, TrainLoop
from repro.runners.train_loop import split_keys
from repro.train.optim import adam
from repro.utils.logger import Logger

CURVE_DIR = os.path.join(os.path.dirname(__file__), "curves")


def _curve_logger(name):
    return Logger(CURVE_DIR, filename=f"{name}.csv",
                  stream=open(os.devnull, "w"))


def _final_return(sampler, params, state):
    state = sampler.reset_stats(state)
    for _ in range(3):
        state, _ = jax.jit(sampler.collect)(params, state)
    return float(sampler.traj_stats(state)["avg_return"])


def _bench_dispatch(rows, *, window=20, reps=5):
    """samples/sec: fused (one scan program per window) vs. per-iteration
    dispatch — on-policy (A2C) and the full off-policy composite (DQN with
    device replay).  Fused must not regress per-iteration dispatch."""
    rng = jax.random.PRNGKey(0)

    def time_loop(tag, loop, ts, ss, rs, steps_per_iter):
        _, keys = split_keys(rng, window)
        out = loop.run_window(ts, ss, rs, keys)   # compile
        jax.block_until_ready(out[3].loss)
        t0 = time.perf_counter()
        ts2, ss2, rs2 = out[:3]
        for _ in range(reps):
            ts2, ss2, rs2, infos, _ = loop.run_window(ts2, ss2, rs2, keys)
        jax.block_until_ready(infos.loss)
        dt = time.perf_counter() - t0
        sps = steps_per_iter * window * reps / dt
        rows.append({"name": f"dispatch_{tag}",
                     "us_per_call": f"{dt / (window * reps) * 1e6:.1f}",
                     "derived": f"sps_{sps:.0f}"})
        return sps

    # on-policy: A2C cartpole
    env = make_env("cartpole")
    model = make_pg_mlp(4, 2)
    agent = make_categorical_pg_agent(model)
    algo = A2C(model.apply, adam(7e-4), distribution=Categorical(2))
    sampler = SerialSampler(env, agent, n_envs=16, horizon=32)
    params = model.init(rng)
    for tag, fuse in (("fused_a2c", True), ("periter_a2c", False)):
        loop = TrainLoop(sampler, algo, fuse=fuse)
        time_loop(tag, loop, algo.init_train_state(rng, params),
                  sampler.init(rng), None, 16 * 32)

    # off-policy composite: DQN catch with device replay
    env = make_env("catch")
    qmodel = make_q_conv(1, 3, img_hw=(10, 5), channels=(16, 32),
                         kernels=(3, 3), strides=(1, 1), d_out=128)
    qagent = make_dqn_agent(qmodel, 3)
    qalgo = DQN(qmodel.apply, adam(5e-4), double=True,
                target_update_interval=100)
    qsampler = SerialSampler(env, qagent, n_envs=16, horizon=16)
    qparams = qmodel.init(rng)
    replay = DeviceReplay(8192, prioritized=True)
    for tag, fuse in (("fused_dqn", True), ("periter_dqn", False)):
        loop = TrainLoop(qsampler, qalgo, replay=replay, batch_size=64,
                         updates_per_collect=2, fuse=fuse)
        rs = replay.init(transition_example(env))
        ss = qsampler.init(rng, {"epsilon": 0.2})
        # prefill so sampled batches are meaningful
        for _ in range(4):
            ss, rs = loop.collect_insert(qparams, ss, rs)
        time_loop(tag, loop, qalgo.init_train_state(rng, qparams),
                  ss, rs, 16 * 16)


_MESH2D_BENCH = """
import dataclasses, time, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.configs import get_smoke_config
from repro.models import backbones as bb
from repro.models import sharding as shd
from repro.envs.token_lm import make_token_lm
from repro.algos.pg.gae import gae_associative
from repro.algos.pg.ppo import make_lm_ppo_train_step
from repro.train.optim import adam, cross_replica, cross_replica_specs
from repro.train.compress import wire_bytes
from repro.launch.mesh import make_2d_mesh, install_2d
from repro.launch.train import make_lm_rollout

B, T, WINDOW, ITERS = 8, 8, 2, 3
cfg = dataclasses.replace(get_smoke_config("gemma2-2b"), unroll=True)
env = make_token_lm(vocab=cfg.vocab, episode_len=T)
rng = jax.random.PRNGKey(0)

def build_batch(traj, v_last):
    adv, ret = gae_associative(traj["reward"], traj["value"], v_last,
                               traj["done"], gamma=0.99, lam=0.95)
    adv = (adv - adv.mean()) / (adv.std() + 1e-8)
    tm = lambda x: jnp.swapaxes(x, 0, 1)
    return {"tokens": tm(traj["tokens"]), "actions": tm(traj["actions"]),
            "logp_old": tm(traj["logp"]), "advantage": tm(adv),
            "return_": tm(ret)}

def bench(name, mesh_shape, compress):
    params = bb.init_lm(rng, cfg)
    if mesh_shape is None:
        shd.set_global_mesh(None)
        opt = adam(3e-4, grad_clip=1.0)
        rollout = make_lm_rollout(cfg, env, B, T)
        train_step = make_lm_ppo_train_step(cfg, opt, entropy_coeff=0.003,
                                            unroll_micro=True)
        def window(params, opt_state, ks):
            for i in range(WINDOW):
                traj, v_last = rollout(params, ks[i])
                params, opt_state, m = train_step(params, opt_state,
                                                  build_batch(traj, v_last))
            return params, opt_state, m
        fn = jax.jit(window)
        opt_state = opt.init(params)
    else:
        n_data, n_model = mesh_shape
        mesh = install_2d(make_2d_mesh(n_data, n_model))
        pspecs = shd.param_pspecs(params, cfg)
        params = jax.device_put(params, shd.make_shardings(pspecs, mesh))
        opt = cross_replica(adam(3e-4, grad_clip=1.0), "data",
                            compress=compress, ef_shards=n_data)
        rollout = make_lm_rollout(cfg, env, B // n_data, T)
        train_step = make_lm_ppo_train_step(cfg, opt, entropy_coeff=0.003,
                                            param_pspecs=pspecs,
                                            unroll_micro=True)
        def window(params, opt_state, ks, sid):
            for i in range(WINDOW):
                traj, v_last = rollout(params,
                                       jax.random.fold_in(ks[i], sid[0]))
                params, opt_state, m = train_step(params, opt_state,
                                                  build_batch(traj, v_last))
            return params, opt_state, jax.lax.pmean(m["loss"], "data")
        ts_spec = cross_replica_specs("data") if compress else P()
        fn0 = jax.jit(jax.shard_map(window, mesh=mesh,
                                    in_specs=(P(), ts_spec, P(), P("data")),
                                    out_specs=(P(), ts_spec, P()),
                                    check_vma=False, axis_names={"data"}))
        sid = jnp.arange(n_data, dtype=jnp.uint32)
        fn = lambda p, o, ks: fn0(p, o, ks, sid)
        opt_state = opt.init(params)
    ks = jax.random.split(jax.random.PRNGKey(1), WINDOW)
    p, o, m = fn(params, opt_state, ks)  # compile
    jax.block_until_ready(jax.tree_util.tree_leaves(p)[0])
    t0 = time.perf_counter()
    for _ in range(ITERS):
        p, o, m = fn(p, o, ks)
    jax.block_until_ready(jax.tree_util.tree_leaves(p)[0])
    dt = (time.perf_counter() - t0) / ITERS
    sps = B * T * WINDOW / dt
    print(f"ROW,{name},{dt / WINDOW * 1e6:.1f},{sps:.0f}_steps_per_sec")
    return params

bench("trainloop_2d_fused_lmppo_1x1", None, None)
bench("trainloop_2d_fused_lmppo_2x2", (2, 2), None)
params = bench("trainloop_2d_fused_lmppo_2x2_int8ef", (2, 2), "int8_ef")
wb = wire_bytes(params)
print(f"ROW,trainloop_2d_int8ef_allreduce,0,"
      f"{wb['bytes_saved']}_bytes_saved_per_step_{wb['ratio']:.2f}x")
"""


def _mesh2d_rows(n_devices: int = 4):
    """LM-PPO fused window on the 2-D mesh, subprocess-forced devices (see
    bench_samplers._sharded_rows for why XLA_FLAGS needs a subprocess)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["JAX_PLATFORMS"] = "cpu"  # forced host devices; the chip stays free
    env["PYTHONPATH"] = os.path.join(repo, "src")
    r = subprocess.run([sys.executable, "-c", _MESH2D_BENCH],
                       capture_output=True, text=True, env=env, timeout=900)
    if r.returncode != 0:
        raise RuntimeError(f"mesh2d bench failed:\n{r.stdout}\n{r.stderr}")
    rows = []
    for line in r.stdout.splitlines():
        if line.startswith("ROW,"):
            _, name, us, derived = line.split(",")
            rows.append({"name": name, "us_per_call": float(us),
                         "derived": derived})
    return rows


def _merge_json(rows, path=None):
    """Merge (not overwrite) rows into BENCH_samplers.json — bench_samplers
    owns the file and rewrites its own keys; these rows ride along (same
    contract as bench_replay)."""
    path = path or os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "BENCH_samplers.json")
    out = {}
    if os.path.exists(path):
        with open(path) as fh:
            out = json.load(fh)
    for r in rows:
        out[r["name"]] = {"us_per_call": r["us_per_call"],
                          "derived": r["derived"]}
    with open(path, "w") as fh:
        json.dump(out, fh, indent=2, sort_keys=True)
        fh.write("\n")


def run():
    rows = []
    rng = jax.random.PRNGKey(0)
    _bench_dispatch(rows)
    rows.extend(_mesh2d_rows())
    _merge_json([r for r in rows if r["name"].startswith("trainloop_2d_")])

    # --- Fig 5 analogue: policy gradient on discrete control ---------------
    for name, algo_cls, kw in [
            ("ppo", PPO, dict(epochs=4, minibatches=4)),
            ("a2c", A2C, dict())]:
        env = make_env("cartpole")
        model = make_pg_mlp(4, 2)
        agent = make_categorical_pg_agent(model)
        algo = algo_cls(model.apply, adam(7e-4, grad_clip=0.5),
                        distribution=Categorical(2), entropy_coeff=0.01, **kw)
        sampler = SerialSampler(env, agent, n_envs=16, horizon=64)
        runner = OnPolicyRunner(sampler, algo, n_iterations=40,
                                log_interval=10,
                                logger=_curve_logger(f"{name}_cartpole"))
        ts, ss, _ = runner.run(rng)
        ret = _final_return(sampler, ts.params, ss)
        rows.append({"name": f"learn_{name}_cartpole",
                     "us_per_call": 0, "derived": f"return_{ret:.0f}"})

    # --- Fig 6 analogue: DQN variants on vision (catch) ---------------------
    for name, kw in [("dqn", dict()),
                     ("double_dueling", dict(dueling=True)),
                     ("c51", dict(n_atoms=21))]:
        env = make_env("catch")
        n_atoms = kw.pop("n_atoms", 0)
        dueling = kw.pop("dueling", False)
        model = make_q_conv(1, 3, img_hw=(10, 5), channels=(16, 32),
                            kernels=(3, 3), strides=(1, 1), d_out=128,
                            dueling=dueling, n_atoms=n_atoms)
        agent = make_dqn_agent(model, 3, n_atoms=n_atoms, v_min=-1, v_max=1)
        algo = DQN(model.apply, adam(5e-4), gamma=0.99, double=True,
                   n_atoms=n_atoms, v_min=-1, v_max=1,
                   target_update_interval=100)
        sampler = SerialSampler(env, agent, n_envs=16, horizon=16)
        runner = OffPolicyRunner(
            sampler, algo, replay_capacity=8192, batch_size=64,
            n_iterations=60, updates_per_collect=2, min_replay=512,
            prioritized=True, log_interval=15,
            logger=_curve_logger(f"{name}_catch"),
            agent_state_kwargs={"epsilon": 0.2})
        ts, ss, _ = runner.run(rng)
        ss = ss._replace(agent_state={"epsilon": jnp.zeros(16)})
        ret = _final_return(sampler, ts.params, ss)
        rows.append({"name": f"learn_{name}_catch",
                     "us_per_call": 0, "derived": f"return_{ret:.2f}"})

    # --- Fig 4 analogue: continuous control (pendulum) ----------------------
    env = make_env("pendulum")
    for name in ("sac", "td3", "ddpg"):
        k1, rng = jax.random.split(rng)
        critic = make_q_critic(3, 1, hidden=(64, 64))
        if name == "sac":
            actor = make_sac_actor(3, 1, hidden=(64, 64))
            agent = make_sac_agent(actor, 1)
            algo = SAC(actor.apply, critic.apply, adam(1e-3), adam(1e-3),
                       act_dim=1)
        else:
            actor = make_ddpg_actor(3, 1, hidden=(64, 64))
            agent = make_ddpg_agent(actor, 1, expl_noise=0.1)
            cls = TD3 if name == "td3" else DDPG
            algo = cls(actor.apply, critic.apply, adam(1e-3), adam(1e-3))
        params = {"actor": actor.init(k1), "critic": critic.init(k1)}
        sampler = SerialSampler(env, agent, n_envs=8, horizon=32)
        runner = OffPolicyRunner(
            sampler, algo, replay_capacity=16384, batch_size=128,
            n_iterations=50, updates_per_collect=4, min_replay=1024,
            log_interval=10, logger=_curve_logger(f"{name}_pendulum"))
        ts, ss, _ = runner.run(rng, params=params)
        ret = _final_return(sampler, ts.params, ss)
        rows.append({"name": f"learn_{name}_pendulum",
                     "us_per_call": 0, "derived": f"return_{ret:.0f}"})
    return rows
