"""Algorithm-level unit tests: DQN targets, C51 projection, R2D1 rescaling,
PPO clipping, SAC/TD3 update mechanics, microbatch invariance."""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from _hypothesis_compat import given, settings, st

from repro.algos import DQN, R2D1, PPO, SAC, TD3, value_rescale, \
    value_rescale_inv
from repro.algos.pg.ppo import make_lm_ppo_train_step
from repro.train.optim import adam
from repro.models.rl_models import (make_q_mlp, make_sac_actor, make_q_critic,
                                    make_ddpg_actor, make_recurrent_q)
from repro.core.distributions import Categorical


@settings(max_examples=100, deadline=None)
@given(st.floats(-1e4, 1e4))
def test_value_rescale_inverse(x):
    y = float(value_rescale_inv(value_rescale(jnp.asarray(x))))
    assert abs(y - x) <= 1e-2 + 1e-3 * abs(x)


@pytest.mark.parametrize("x", [-1e4, -123.4, -1.0, 0.0, 0.5, 77.7, 1e4])
def test_value_rescale_inverse_points(x):
    """Deterministic fallback coverage when hypothesis is absent."""
    y = float(value_rescale_inv(value_rescale(jnp.asarray(x))))
    assert abs(y - x) <= 1e-2 + 1e-3 * abs(x)


def test_dqn_target_handmade(rng):
    """1-step double-DQN target on a fabricated batch."""
    model = make_q_mlp(2, 3, hidden=(8,))
    params = model.init(rng)
    algo = DQN(model.apply, adam(1e-3), gamma=0.5, double=True)
    batch = {
        "observation": jnp.ones((4, 2)),
        "action": jnp.asarray([0, 1, 2, 0]),
        "return_": jnp.asarray([1.0, 2.0, 3.0, 4.0]),
        "bootstrap": jnp.asarray([1.0, 0.0, 1.0, 1.0]),
        "next_observation": jnp.ones((4, 2)) * 2,
        "n_used": jnp.ones(4, jnp.int32),
        "is_weights": jnp.ones(4),
    }
    loss, aux = algo.loss(params, params, batch)
    q = model.apply(params, batch["observation"])
    qa = np.asarray(q)[np.arange(4), np.asarray(batch["action"])]
    qn = np.asarray(model.apply(params, batch["next_observation"]))
    a_star = qn.argmax(-1)
    target = np.asarray(batch["return_"]) + 0.5 * np.asarray(
        batch["bootstrap"]) * qn[np.arange(4), a_star]
    td = qa - target
    # huber with delta=1
    expect = np.where(np.abs(td) <= 1, 0.5 * td**2, np.abs(td) - 0.5).mean()
    np.testing.assert_allclose(float(loss), expect, rtol=1e-5)


def test_c51_projection_probability_mass(rng):
    model = make_q_mlp(2, 3, hidden=(8,), n_atoms=11)
    params = model.init(rng)
    algo = DQN(model.apply, adam(1e-3), n_atoms=11, v_min=-2, v_max=2,
               gamma=0.9)
    batch = {
        "observation": jax.random.normal(rng, (6, 2)),
        "action": jnp.zeros(6, jnp.int32),
        "return_": jnp.linspace(-3, 3, 6),
        "bootstrap": jnp.ones(6),
        "next_observation": jax.random.normal(rng, (6, 2)),
        "n_used": jnp.ones(6, jnp.int32),
        "is_weights": jnp.ones(6),
    }
    loss, aux = algo.loss(params, params, batch)
    assert np.isfinite(float(loss)) and float(loss) > 0


def test_dqn_update_moves_toward_target(rng):
    model = make_q_mlp(3, 2, hidden=(16,))
    params = model.init(rng)
    algo = DQN(model.apply, adam(1e-2), gamma=0.0)  # target == return
    ts = algo.init_train_state(rng, params)
    batch = {
        "observation": jnp.tile(jnp.asarray([[1.0, 0.0, -1.0]]), (8, 1)),
        "action": jnp.zeros(8, jnp.int32),
        "return_": jnp.full(8, 5.0),
        "bootstrap": jnp.zeros(8),
        "next_observation": jnp.zeros((8, 3)),
        "n_used": jnp.ones(8, jnp.int32),
        "is_weights": jnp.ones(8),
    }
    upd = jax.jit(algo.update)
    for _ in range(200):
        ts, info = upd(ts, batch, rng)
    q = model.apply(ts.params, batch["observation"][:1])
    np.testing.assert_allclose(float(q[0, 0]), 5.0, atol=0.2)


def test_ppo_clip_zero_gradient_when_ratio_far(rng):
    """Clipped surrogate has zero policy gradient when the ratio is outside
    the clip range and the advantage pushes it further."""
    dist = Categorical(2)

    def apply_fn(params, obs, pa, pr):
        logits = jnp.stack([params["w"] * jnp.ones(obs.shape[0]),
                            jnp.zeros(obs.shape[0])], -1)
        return logits, jnp.zeros(obs.shape[0])

    algo = PPO(apply_fn, adam(1e-2), distribution=dist, clip_eps=0.1,
               entropy_coeff=0.0, value_coeff=0.0, normalize_advantage=False)
    params = {"w": jnp.asarray(2.0)}
    mb = {
        "observation": jnp.zeros((4, 1)),
        "action": jnp.zeros(4, jnp.int32),
        # logp_old chosen so ratio >> 1+eps, positive advantage
        "logp_old": jnp.full(4, -5.0),
        "advantage": jnp.ones(4),
        "return_": jnp.zeros(4),
        "value": jnp.zeros(4),
    }
    g = jax.grad(lambda p: algo.loss(p, mb)[0])(params)
    np.testing.assert_allclose(float(g["w"]), 0.0, atol=1e-7)


def test_td3_delayed_policy_update(rng):
    actor = make_ddpg_actor(3, 1, hidden=(8,))
    critic = make_q_critic(3, 1, hidden=(8,))
    algo = TD3(actor.apply, critic.apply, adam(1e-3), adam(1e-3),
               policy_delay=2)
    params = {"actor": actor.init(rng), "critic": critic.init(rng)}
    ts = algo.init_train_state(rng, params)
    batch = {
        "observation": jax.random.normal(rng, (8, 3)),
        "action": jnp.clip(jax.random.normal(rng, (8, 1)), -1, 1),
        "return_": jnp.ones(8),
        "bootstrap": jnp.ones(8),
        "next_observation": jax.random.normal(rng, (8, 3)),
        "n_used": jnp.ones(8, jnp.int32),
        "is_weights": jnp.ones(8),
    }
    upd = jax.jit(algo.update)
    ts1, _ = upd(ts, batch, rng)      # step 1: actor frozen
    same = jax.tree_util.tree_map(
        lambda a, b: bool(jnp.allclose(a, b)), ts.params["actor"],
        ts1.params["actor"])
    assert all(jax.tree_util.tree_leaves(same))
    ts2, _ = upd(ts1, batch, rng)     # step 2: actor moves
    moved = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), ts1.params["actor"],
        ts2.params["actor"])
    assert max(jax.tree_util.tree_leaves(moved)) > 0


def test_sac_alpha_autotuning_direction(rng):
    """If policy entropy is far below target, alpha must increase."""
    actor = make_sac_actor(3, 1, hidden=(8,))
    critic = make_q_critic(3, 1, hidden=(8,))
    algo = SAC(actor.apply, critic.apply, adam(1e-3), adam(1e-3), act_dim=1,
               target_entropy=5.0, alpha_lr=0.1)  # unreachably high target
    params = {"actor": actor.init(rng), "critic": critic.init(rng)}
    ts = algo.init_train_state(rng, params)
    batch = {
        "observation": jax.random.normal(rng, (16, 3)),
        "action": jnp.clip(jax.random.normal(rng, (16, 1)), -1, 1),
        "return_": jnp.zeros(16),
        "bootstrap": jnp.ones(16),
        "next_observation": jax.random.normal(rng, (16, 3)),
        "n_used": jnp.ones(16, jnp.int32),
        "is_weights": jnp.ones(16),
    }
    a0 = float(jnp.exp(ts.extra["log_alpha"]))
    upd = jax.jit(algo.update)
    for _ in range(5):
        rng, k = jax.random.split(rng)
        ts, info = upd(ts, batch, k)
    assert float(jnp.exp(ts.extra["log_alpha"])) > a0


def test_r2d1_loss_runs_and_priorities_shape(rng):
    model = make_recurrent_q(3, 2, conv=False, d_lstm=8, trunk_hidden=(8,))
    params = model.init(rng)
    algo = R2D1(model.apply, adam(1e-3), burn_in=2, n_step=2)
    L, batch_n = 10, 4
    from repro.replay.host import SequenceSamples
    seq = SequenceSamples(
        observation=jax.random.normal(rng, (batch_n, L + 1, 3)),
        prev_action=jnp.zeros((batch_n, L + 1), jnp.int32),
        prev_reward=jnp.zeros((batch_n, L + 1)),
        action=jnp.zeros((batch_n, L + 1), jnp.int32),
        reward=jnp.ones((batch_n, L + 1)),
        done=jnp.zeros((batch_n, L + 1), bool),
        init_state=None)
    batch = {"sequence": seq,
             "init_state": model.initial_state(batch_n),
             "is_weights": jnp.ones(batch_n)}
    loss, aux = algo.loss(params, params, batch)
    assert np.isfinite(float(loss))
    assert aux["td_abs_max"].shape == (batch_n,)
    assert aux["td_abs_mean"].shape == (batch_n,)


def test_lm_ppo_microbatch_invariance(rng):
    """Gradient accumulation: n_micro=1 and n_micro=2 produce the same
    accumulated gradient (the memory knob must not change the math).
    SGD update isolates the raw gradient (Adam's sign normalization would
    amplify bf16 summation-order noise on near-zero grads)."""
    from repro.configs import get_smoke_config
    from repro.models import backbones as bb
    from repro.train.optim import sgd
    cfg = get_smoke_config("glm4-9b")
    params = bb.init_lm(rng, cfg)
    opt = sgd(1.0)
    batch = {
        "tokens": jax.random.randint(rng, (4, 16), 0, cfg.vocab),
        "actions": jax.random.randint(rng, (4, 16), 0, cfg.vocab),
        "logp_old": jnp.full((4, 16), -3.0),
        "advantage": jax.random.normal(rng, (4, 16)),
        "return_": jax.random.normal(rng, (4, 16)),
    }
    outs, metrics = [], []
    for n_micro in (1, 2):
        step = make_lm_ppo_train_step(cfg, opt, n_microbatches=n_micro)
        p2, _, m = jax.jit(step)(params, opt.init(params), batch)
        outs.append(p2)
        metrics.append(m)
    # params_after = params - grad: compare the implied gradients
    diffs = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), outs[0], outs[1])
    # bf16 forward: summation order across micro splits costs ~1e-3 rel
    assert max(jax.tree_util.tree_leaves(diffs)) < 3e-3
    assert abs(float(metrics[0]["loss"]) - float(metrics[1]["loss"])) < 1e-5


def test_lm_rollout_and_update_name_their_device_ops(rng):
    """Every operation of the compiled LM rollout and PPO update carries
    its scope (``lm_rollout``, ``ppo_update``) in its op_name, by which a
    device trace can attribute time."""
    import re
    from repro.configs import get_smoke_config
    from repro.envs.token_lm import make_token_lm
    from repro.launch.train import make_lm_rollout
    from repro.models import backbones as bb
    from repro.train.optim import adam
    cfg = get_smoke_config("mamba2-1.3b")
    env = make_token_lm(vocab=cfg.vocab, episode_len=8)
    params = jax.eval_shape(lambda k: bb.init_lm(k, cfg), rng)
    roll = jax.jit(make_lm_rollout(cfg, env, 2, 8)).lower(params, rng)
    opt = adam(1e-3)
    batch = {k: jax.ShapeDtypeStruct((2, 8), jnp.int32 if k in (
        "tokens", "actions") else jnp.float32)
        for k in ("tokens", "actions", "logp_old", "advantage", "return_")}
    step = jax.jit(make_lm_ppo_train_step(cfg, opt)).lower(
        params, jax.eval_shape(opt.init, params), batch)
    for lowered, scope in ((roll, "lm_rollout"), (step, "ppo_update")):
        names = [n for n in re.findall(r'op_name="([^"]*)"',
                                       lowered.compile().as_text())
                 if n.startswith("jit(")]
        assert names and all(f")/{scope}" in n for n in names), scope
