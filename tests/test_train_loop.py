"""TrainLoop + thin runners: scan-fused window equivalence, off-policy
checkpoint restart (start_iter regression), sharded sampler stats
round-trip."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.envs import make_env
from repro.agents import (make_categorical_pg_agent, make_dqn_agent,
                          make_ddpg_agent, make_sac_agent)
from repro.algos import A2C, DQN, SAC, TD3, DDPG
from repro.core.distributions import Categorical
from repro.models.rl_models import (make_pg_mlp, make_q_conv, make_sac_actor,
                                    make_ddpg_actor, make_q_critic)
from repro.samplers import SerialSampler
from repro.runners import OnPolicyRunner, OffPolicyRunner
from conftest import run_with_devices


class _Null:
    def record(self, *a, **k):
        pass


def _max_diff(a, b):
    d = jax.tree_util.tree_map(lambda x, y: float(jnp.max(jnp.abs(x - y))),
                               a, b)
    return max(jax.tree_util.tree_leaves(d))


def _onpolicy_runner(fuse, **kw):
    env = make_env("cartpole")
    model = make_pg_mlp(4, 2)
    agent = make_categorical_pg_agent(model)
    algo = A2C(model.apply, _adam(), distribution=Categorical(2))
    sampler = SerialSampler(env, agent, n_envs=4, horizon=8)
    return OnPolicyRunner(sampler, algo, logger=_Null(), fuse=fuse, **kw)


def _offpolicy_runner(fuse, **kw):
    env = make_env("catch")
    model = make_q_conv(1, 3, img_hw=(10, 5), channels=(8,), kernels=(3,),
                        strides=(1,), d_out=32)
    agent = make_dqn_agent(model, 3)
    algo = DQN(model.apply, _adam(), double=True, target_update_interval=50)
    sampler = SerialSampler(env, agent, n_envs=4, horizon=8)
    kw.setdefault("replay_capacity", 512)
    kw.setdefault("batch_size", 32)
    kw.setdefault("updates_per_collect", 2)
    kw.setdefault("min_replay", 64)
    kw.setdefault("prioritized", True)
    kw.setdefault("agent_state_kwargs", {"epsilon": 0.2})
    return OffPolicyRunner(sampler, algo, logger=_Null(), fuse=fuse, **kw)


def _adam():
    from repro.train.optim import adam
    return adam(1e-3)


def test_fused_matches_periter_onpolicy(rng):
    """The scan-fused window and per-iteration dispatch are the SAME
    program modulo batching: identical rng stream -> identical params."""
    ts_f, _, _ = _onpolicy_runner(True, n_iterations=6, log_interval=3).run(rng)
    ts_u, _, _ = _onpolicy_runner(False, n_iterations=6, log_interval=3).run(rng)
    assert int(ts_f.step) == 6
    assert _max_diff(ts_f.params, ts_u.params) == 0.0


def test_fused_matches_periter_offpolicy(rng):
    ts_f, _, _ = _offpolicy_runner(True, n_iterations=4, log_interval=2).run(rng)
    ts_u, _, _ = _offpolicy_runner(False, n_iterations=4, log_interval=2).run(rng)
    assert int(ts_f.step) == 8  # 4 iterations x 2 updates
    assert _max_diff(ts_f.params, ts_u.params) == 0.0


@pytest.mark.parametrize("name", ["sac", "td3", "ddpg"])
def test_qpg_family_through_trainloop(rng, name):
    """The Q-value policy-gradient family runs the same fused TrainLoop as
    DQN — all three paper families share one runner path via BatchSpec."""
    env = make_env("pendulum")
    actor = (make_sac_actor if name == "sac" else make_ddpg_actor)(
        3, 1, hidden=(8,))
    critic = make_q_critic(3, 1, hidden=(8,))
    if name == "sac":
        agent = make_sac_agent(actor, 1)
        algo = SAC(actor.apply, critic.apply, _adam(), _adam(), act_dim=1)
    else:
        agent = make_ddpg_agent(actor, 1, expl_noise=0.1)
        cls = TD3 if name == "td3" else DDPG
        algo = cls(actor.apply, critic.apply, _adam(), _adam())
    sampler = SerialSampler(env, agent, n_envs=4, horizon=16)
    params = {"actor": actor.init(rng), "critic": critic.init(rng)}
    runner = OffPolicyRunner(sampler, algo, replay_capacity=512,
                             batch_size=32, n_iterations=2,
                             updates_per_collect=2, min_replay=64,
                             log_interval=2, logger=_Null())
    ts, ss, info = runner.run(rng, params=params)
    assert int(ts.step) == 4
    assert np.isfinite(float(info.loss))


def test_offpolicy_restore_honors_start_iter(tmp_path, rng):
    """Regression: OffPolicyRunner.run must resume from the checkpoint's
    iteration, not loop from 0 (seed bug: start_iter read but ignored)."""
    ckpt = str(tmp_path)
    r1 = _offpolicy_runner(True, n_iterations=4, log_interval=2,
                           ckpt_dir=ckpt, ckpt_interval=2,
                           updates_per_collect=1)
    ts1, _, _ = r1.run(rng)
    assert int(ts1.step) == 4

    r2 = _offpolicy_runner(True, n_iterations=6, log_interval=2,
                           ckpt_dir=ckpt, ckpt_interval=2,
                           updates_per_collect=1)
    ts2, _, _ = r2.run(rng, restore=True)
    # resumed at iteration 4 -> exactly 2 more updates (buggy: 4 + 6 = 10)
    assert int(ts2.step) == 6


def test_onpolicy_restore_still_works(tmp_path, rng):
    ckpt = str(tmp_path)
    r1 = _onpolicy_runner(True, n_iterations=4, log_interval=2,
                          ckpt_dir=ckpt, ckpt_interval=2)
    ts1, _, _ = r1.run(rng)
    r2 = _onpolicy_runner(True, n_iterations=6, log_interval=2,
                          ckpt_dir=ckpt, ckpt_interval=2)
    ts2, _, _ = r2.run(rng, restore=True)
    assert int(ts2.step) == 6


def test_trainloop_rejects_missing_pieces(rng):
    from repro.runners import TrainLoop
    env = make_env("cartpole")
    model = make_pg_mlp(4, 2)
    agent = make_categorical_pg_agent(model)
    sampler = SerialSampler(env, agent, n_envs=2, horizon=4)

    class NoSpec:
        batch_spec = None
    with pytest.raises(ValueError):
        TrainLoop(sampler, NoSpec())

    algo = DQN(model.apply, _adam())
    with pytest.raises(ValueError):
        TrainLoop(sampler, algo)  # replayed algo without device replay

    from repro.algos import R2D1
    from repro.replay.interface import DeviceReplay
    r2d1 = R2D1(model.apply, _adam())
    with pytest.raises(ValueError):
        # sequence mode needs host sequence replay (AsyncR2D1Runner)
        TrainLoop(sampler, r2d1, replay=DeviceReplay(64), batch_size=8)


def test_sharded_traj_stats_roundtrip():
    """ShardedSampler episode stats: psum'd accumulation across shards,
    reset_stats zeroes them, accumulation resumes after reset."""
    run_with_devices("""
import jax, jax.numpy as jnp
from repro.envs import make_env
from repro.agents import make_categorical_pg_agent
from repro.models.rl_models import make_pg_mlp
from repro.samplers.sharded import ShardedSampler
from repro.launch.mesh import auto_mesh
mesh = auto_mesh((4,), ("data",))
env = make_env("cartpole")
model = make_pg_mlp(4, 2)
agent = make_categorical_pg_agent(model)
s = ShardedSampler(env, agent, n_envs=8, horizon=32, mesh=mesh)
rng = jax.random.PRNGKey(0)
params = model.init(rng)
state = s.init(rng)
for _ in range(4):
    state, _ = s.collect(params, state)
stats = s.traj_stats(state)
assert int(stats["episodes"]) > 0, stats
assert float(stats["avg_len"]) > 0
state = s.reset_stats(state)
zeroed = s.traj_stats(state)
assert int(zeroed["episodes"]) == 0
assert float(state.completed_return_sum) == 0.0
state, _ = s.collect(params, state)   # accumulation resumes post-reset
again = s.traj_stats(state)
assert int(again["episodes"]) >= 0 and float(state.completed_len_sum) >= 0
print("sharded stats roundtrip ok")
""", n_devices=4)
