"""Plain reference of LM-PPO steps: the token-MDP rewards, GAE, the clipped
PPO loss, global-norm clipping and Adam, in float32.

It follows the program's training as ``launch/train.py`` configures it:
one gradient step per rollout of batch x horizon tokens, advantages
normalised over the batch, the rollout's policy over the first ``vocab``
logits and the update's over all padded ones (as the program does).  The
rollout's tokens are taken as data: the reference scores the actions the
program sampled, it does not sample.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import mamba2

F32 = jnp.float32


def token_lm_rewards(obs, actions, vocab: int, chain_seed: int = 0,
                     temp: float = 1.0):
    """Reward of each transition obs -> action: its log-probability under
    the environment's fixed random chain, whose row for token t is
    log_softmax(temp * normal(fold_in(PRNGKey(chain_seed), t), (vocab,)))."""
    base = jax.random.PRNGKey(chain_seed)

    def one(t, a):
        row = jax.nn.log_softmax(
            temp * jax.random.normal(jax.random.fold_in(base, t), (vocab,)))
        return row[a]

    return jax.vmap(jax.vmap(one))(obs, actions)


def gae(rewards, values, done, gamma: float, lam: float):
    """Time-major (T, B); the episode ends at the last step, so nothing is
    bootstrapped past it.  Returns (advantages, returns)."""
    T = rewards.shape[0]
    adv, nxt_adv, nxt_val = [], jnp.zeros_like(rewards[0]), jnp.zeros_like(
        values[0])
    for t in reversed(range(T)):
        nd = 1.0 - done[t]
        delta = rewards[t] + gamma * nxt_val * nd - values[t]
        nxt_adv = delta + gamma * lam * nd * nxt_adv
        nxt_val = values[t]
        adv.append(nxt_adv)
    adv = jnp.stack(adv[::-1])
    return adv, adv + values


def rollout_quantities(params, obs, actions, vocab: int, quant: bool):
    """obs/actions (B, T) -> logp of each action under the rollout's
    policy (first ``vocab`` logits) and the values, (B, T) each."""
    logits, values = mamba2.logits_values(params, obs, quant=quant)
    logp = jax.nn.log_softmax(logits[..., :vocab], axis=-1)
    return jnp.take_along_axis(logp, actions[..., None], -1)[..., 0], values


def ppo_loss(params, batch, hp, quant: bool):
    logits, values = mamba2.logits_values(params, batch["obs"], quant=quant,
                                          remat=True)
    logp_all = jax.nn.log_softmax(logits, axis=-1)
    logp = jnp.take_along_axis(logp_all, batch["actions"][..., None],
                               -1)[..., 0]
    ratio = jnp.exp(logp - batch["logp_old"])
    adv, eps = batch["advantage"], hp["clip_eps"]
    surr = jnp.minimum(ratio * adv, jnp.clip(ratio, 1 - eps, 1 + eps) * adv)
    v_loss = 0.5 * jnp.mean(jnp.square(values - batch["return_"]))
    ent = -jnp.mean(jnp.sum(jnp.exp(logp_all) * logp_all, axis=-1))
    return -jnp.mean(surr) + hp["value_coeff"] * v_loss \
        - hp["entropy_coeff"] * ent


def adam_step(params, grads, mu, nu, step, hp):
    """Global-norm clipping, then Adam with bias correction."""
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                         for g in jax.tree_util.tree_leaves(grads)))
    scale = jnp.minimum(1.0, hp["grad_clip"] / jnp.maximum(gnorm, 1e-9))
    b1, b2, eps, lr = hp["b1"], hp["b2"], hp["adam_eps"], hp["lr"]
    bc1, bc2 = 1 - b1 ** step, 1 - b2 ** step

    def upd(p, g, m, v):
        g = g * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        return p - lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps), m, v

    out = jax.tree_util.tree_map(upd, params, grads, mu, nu)
    pick = lambda i: jax.tree_util.tree_map(  # noqa: E731
        lambda t: t[i], out, is_leaf=lambda x: isinstance(x, tuple))
    return pick(0), pick(1), pick(2)


def leaf_norms(tree):
    return {jax.tree_util.keystr(path): jnp.linalg.norm(leaf.ravel())
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def follow(make_params, steps, hp, *, vocab: int, quant: bool = False):
    """Follow the program's first steps from the initial weights that
    ``make_params()`` makes.  ``steps`` is a list of (obs, actions), each
    (B, T) int32: the rollout tokens of one step.  Returns per-step losses,
    per-step rollout logp (B, T), and the leaf norms of Adam's first moment
    and of the parameters' change after the last step."""

    @jax.jit
    def prepare(params, obs, actions):
        logp_old, values = rollout_quantities(params, obs, actions, vocab,
                                              quant)
        tm = lambda x: jnp.swapaxes(x, 0, 1)  # noqa: E731
        rewards = token_lm_rewards(obs, actions, vocab)
        done = jnp.zeros(tm(rewards).shape, F32).at[-1].set(1.0)
        adv, ret = gae(tm(rewards), tm(values), done, hp["gamma"], hp["lam"])
        adv = (adv - adv.mean()) / (adv.std() + 1e-8)
        return {"obs": obs, "actions": actions, "logp_old": logp_old,
                "advantage": tm(adv), "return_": tm(ret)}

    grad = jax.jit(lambda p, b: jax.value_and_grad(ppo_loss)(p, b, hp, quant))
    update = jax.jit(lambda p, g, m, v, step: adam_step(p, g, m, v, step, hp),
                     donate_argnums=(0, 2, 3))
    change = jax.jit(lambda p, p0: leaf_norms(
        jax.tree_util.tree_map(lambda a, b: a - b, p, p0)))

    params = make_params()
    mu = jax.tree_util.tree_map(jnp.zeros_like, params)
    nu = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, logps = [], []
    for i, (obs, actions) in enumerate(steps):
        batch = prepare(params, obs, actions)
        loss, grads = grad(params, batch)
        logps.append(batch["logp_old"])
        losses.append(loss)
        params, mu, nu = update(params, grads, mu, nu,
                                jnp.asarray(i + 1, F32))
        del grads, batch
    mu_norms = jax.jit(leaf_norms)(mu)
    del mu, nu
    change_norms = change(params, make_params())
    return {"loss": [float(x) for x in losses],
            "logp": [jax.device_get(x) for x in logps],
            "mu_norms": jax.device_get(mu_norms),
            "change_norms": jax.device_get(change_norms)}
