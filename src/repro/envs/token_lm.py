"""Token MDP: the RLHF-style environment where the policy IS a language model.

A fixed random Markov chain over the vocabulary plays "environment": the
observation is the current token, the action is the next token, and the reward
is the log-probability of that transition under the chain (so the optimal
policy matches the chain's conditional argmax, and expected reward has a known
upper bound).  Batched action selection over this env is exactly LM decoding;
the paper's serving machinery runs unchanged.

Each token's row of transition logits is drawn from its own key when the
env steps, so a full-size vocabulary never materializes the (V, V) table
(at V=50k it would be 10 GB).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.spaces import Discrete
from .base import EnvSpec, EnvInfo


def _chain_row(tok, vocab: int, temp: float, seed: int):
    """Log-probs (V,) of the chain's transitions out of ``tok``."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), tok)
    return jax.nn.log_softmax(temp * jax.random.normal(key, (vocab,)))


def make_token_lm(vocab: int = 256, episode_len: int = 64, temp: float = 1.0,
                  seed: int = 0) -> EnvSpec:

    def _fresh(rng):
        tok = jax.random.randint(rng, (), 0, vocab)
        return {"tok": tok, "t": jnp.zeros((), jnp.int32)}

    def reset(rng):
        s = _fresh(rng)
        return s, s["tok"]

    def step(state, action, rng):
        a = action.astype(jnp.int32)
        reward = _chain_row(state["tok"], vocab, temp, seed)[a]
        t = state["t"] + 1
        timeout = t >= episode_len
        done = timeout
        fresh = _fresh(rng)
        tok = jnp.where(done, fresh["tok"], a)
        t = jnp.where(done, 0, t)
        info = EnvInfo(timeout=timeout, episode_step=t, terminal_obs=a)
        return {"tok": tok, "t": t}, tok, reward, done, info

    return EnvSpec(
        name="token_lm",
        reset=reset,
        step=step,
        observation_space=Discrete(vocab),
        action_space=Discrete(vocab),
        max_episode_steps=episode_len,
    )


def chain_log_probs(vocab: int = 256, temp: float = 1.0, seed: int = 0):
    """The env's true transition log-probs (V, V) — for computing the optimal
    expected reward (greedy upper bound) in tests and learning curves."""
    return jax.vmap(lambda t: _chain_row(t, vocab, temp, seed))(
        jnp.arange(vocab))
