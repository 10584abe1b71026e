"""Optimizers from scratch (no optax): Adam/AdamW + SGD, global-norm clip,
LR schedules, Polyak target-network updates.

Adam moments are fp32 trees with the SAME structure as params, so whatever
PartitionSpec tree shards the params shards the optimizer state (ZeRO-1 comes
from the fsdp axis in sharding rules, not from optimizer code).
"""
from __future__ import annotations

import math
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .compress import EFState, cross_pod_allreduce

F32 = jnp.float32


class OptState(NamedTuple):
    step: jnp.ndarray
    mu: Any
    nu: Any


class CrossReplicaState(NamedTuple):
    """State of a compressed cross_replica optimizer: the wrapped optimizer's
    state plus the error-feedback residual (one per shard of the compressed
    axis — leaves carry a leading shard dim, locally 1 inside shard_map) and
    two replicated health scalars the telemetry sentinels read."""
    inner: Any
    ef: EFState
    shard_grad_norm: jnp.ndarray   # pmax over shards of pre-reduce grad norm
    ef_err_norm: jnp.ndarray       # global Frobenius norm of the residual


class Optimizer(NamedTuple):
    init: Callable  # params -> OptState
    update: Callable  # (grads, state, params) -> (new_params, new_state)


# ---------------------------------------------------------------------------
# schedules
# ---------------------------------------------------------------------------

def constant(lr: float):
    return lambda step: jnp.asarray(lr, F32)


def linear_warmup_cosine(peak_lr: float, warmup: int, total: int,
                         final_frac: float = 0.1):
    def sched(step):
        step = step.astype(F32)
        warm = peak_lr * jnp.minimum(step / max(warmup, 1), 1.0)
        prog = jnp.clip((step - warmup) / max(total - warmup, 1), 0.0, 1.0)
        cos = peak_lr * (final_frac + (1 - final_frac) * 0.5 * (1 + jnp.cos(jnp.pi * prog)))
        return jnp.where(step < warmup, warm, cos)
    return sched


# ---------------------------------------------------------------------------
# gradient utilities
# ---------------------------------------------------------------------------

def global_norm(tree):
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(F32))) for l in leaves))


def clip_by_global_norm(tree, max_norm: float):
    norm = global_norm(tree)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree_util.tree_map(lambda g: g * scale.astype(g.dtype), tree), norm


# ---------------------------------------------------------------------------
# Adam / AdamW
# ---------------------------------------------------------------------------

def adam(lr, b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
         weight_decay: float = 0.0, grad_clip: Optional[float] = None) -> Optimizer:
    sched = lr if callable(lr) else constant(lr)

    def init(params):
        # zeros_like: each moment takes its parameter's sharding
        zeros = lambda: jax.tree_util.tree_map(
            lambda p: jnp.zeros_like(p, F32), params)
        return OptState(step=jnp.zeros((), jnp.int32), mu=zeros(), nu=zeros())

    def update(grads, state: OptState, params):
        if grad_clip is not None:
            grads, gnorm = clip_by_global_norm(grads, grad_clip)
        else:
            gnorm = global_norm(grads)
        step = state.step + 1
        lr_t = sched(step)
        bc1 = 1 - b1 ** step.astype(F32)
        bc2 = 1 - b2 ** step.astype(F32)

        def upd(p, g, m, v):
            g = g.astype(F32)
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * jnp.square(g)
            mhat = m / bc1
            vhat = v / bc2
            delta = mhat / (jnp.sqrt(vhat) + eps)
            if weight_decay:
                delta = delta + weight_decay * p.astype(F32)
            return (p.astype(F32) - lr_t * delta).astype(p.dtype), m, v

        flat = jax.tree_util.tree_map(upd, params, grads, state.mu, state.nu)
        new_params = jax.tree_util.tree_map(lambda t: t[0], flat,
                                            is_leaf=lambda x: isinstance(x, tuple))
        mu = jax.tree_util.tree_map(lambda t: t[1], flat,
                                    is_leaf=lambda x: isinstance(x, tuple))
        nu = jax.tree_util.tree_map(lambda t: t[2], flat,
                                    is_leaf=lambda x: isinstance(x, tuple))
        return new_params, OptState(step=step, mu=mu, nu=nu), gnorm

    return Optimizer(init, update)


def sgd(lr, momentum: float = 0.0, grad_clip: Optional[float] = None) -> Optimizer:
    sched = lr if callable(lr) else constant(lr)

    def init(params):
        mu = jax.tree_util.tree_map(lambda p: jnp.zeros_like(p, F32), params)
        return OptState(step=jnp.zeros((), jnp.int32), mu=mu, nu=None)

    def update(grads, state: OptState, params):
        if grad_clip is not None:
            grads, gnorm = clip_by_global_norm(grads, grad_clip)
        else:
            gnorm = global_norm(grads)
        step = state.step + 1
        lr_t = sched(step)

        def upd(p, g, m):
            m = momentum * m + g.astype(F32)
            return (p.astype(F32) - lr_t * m).astype(p.dtype), m

        flat = jax.tree_util.tree_map(upd, params, grads, state.mu)
        new_params = jax.tree_util.tree_map(lambda t: t[0], flat,
                                            is_leaf=lambda x: isinstance(x, tuple))
        mu = jax.tree_util.tree_map(lambda t: t[1], flat,
                                    is_leaf=lambda x: isinstance(x, tuple))
        return new_params, OptState(step=step, mu=mu, nu=None), gnorm

    return Optimizer(init, update)


def cross_replica(opt: Optimizer, axis, *, compress: Optional[str] = None,
                  ef_shards: int = 1) -> Optimizer:
    """Data-parallel wrapper: all-reduce grads over ``axis`` before the inner
    update (paper §2.4 synchronous multi-GPU — "gradients all-reduced").

    Because every loss in the repo is a mean over its (shard-local) batch,
    pmean of per-shard grads equals the gradient of the global-batch mean,
    so the wrapped update — run replicated inside ``shard_map`` — is the
    SAME update the serial loop takes on the full batch.  Clipping and the
    reported grad norm see the reduced grads, matching serial semantics.
    Idempotent: wrapping twice with the same (axis, compress) is a no-op.

    ``axis`` may be a tuple of mesh axis names: with ``compress=None`` the
    pmean spans all of them in one collective; with ``compress="int8_ef"``
    the reduction grows a SECOND stage — full-precision pmean over the inner
    axes (``axis[1:]``, the in-pod links), then int8 error-feedback
    all-reduce (train/compress.py cross_pod_allreduce) over the outermost
    axis (the scarce cross-pod links).  A single ``axis`` string with
    compression routes the whole reduction through the compressor — the
    (data x model) LM mesh case, where 'data' IS the cross-pod axis.

    Compression carries state: the returned optimizer's ``init`` wraps the
    inner state in :class:`CrossReplicaState` holding the per-shard EF
    residual.  ``ef_shards`` sizes the residual's leading shard dim — pass
    the extent of the compressed axis so each shard of a ``shard_map`` owns
    one residual slice (in/out specs from :func:`cross_replica_specs`).
    """
    tag = (tuple(axis) if not isinstance(axis, str) else axis, compress)
    if getattr(opt.update, "_cross_replica_axis", None) == tag:
        return opt
    axes = (axis,) if isinstance(axis, str) else tuple(axis)

    if compress is None:
        def update(grads, state, params):
            grads = jax.tree_util.tree_map(
                lambda g: jax.lax.pmean(g, axes), grads)
            return opt.update(grads, state, params)

        update._cross_replica_axis = tag
        return Optimizer(opt.init, update)

    if compress != "int8_ef":
        raise ValueError(f"unknown compress mode {compress!r} "
                         f"(supported: 'int8_ef')")
    outer, inner_axes = axes[0], axes[1:]

    def init(params):
        residual = jax.tree_util.tree_map(
            lambda p: jnp.zeros((ef_shards,) + p.shape, F32), params)
        return CrossReplicaState(
            inner=opt.init(params), ef=EFState(residual=residual),
            shard_grad_norm=jnp.zeros((), F32),
            ef_err_norm=jnp.zeros((), F32))

    def update(grads, state: CrossReplicaState, params):
        if inner_axes:  # stage 1: full-precision in-pod reduction
            grads = jax.tree_util.tree_map(
                lambda g: jax.lax.pmean(g, inner_axes), grads)
        local_norm = global_norm(grads)
        # stage 2: int8 + error feedback over the outermost (cross-pod) axis;
        # the residual's leading shard dim is 1 in the local view
        res = jax.tree_util.tree_map(lambda r: r[0], state.ef.residual)
        grads, ef = cross_pod_allreduce(grads, EFState(residual=res),
                                        axis=outer)
        res_new = jax.tree_util.tree_map(lambda r: r[None], ef.residual)
        err_sq = sum(jnp.sum(jnp.square(l))
                     for l in jax.tree_util.tree_leaves(ef.residual))
        new_params, inner_state, gnorm = opt.update(grads, state.inner, params)
        new_state = CrossReplicaState(
            inner=inner_state, ef=EFState(residual=res_new),
            shard_grad_norm=jax.lax.pmax(local_norm, outer),
            ef_err_norm=jnp.sqrt(jax.lax.psum(err_sq, outer)))
        return new_params, new_state, gnorm

    update._cross_replica_axis = tag
    return Optimizer(init, update)


def cross_replica_specs(axis: str) -> CrossReplicaState:
    """shard_map in/out spec prefix for a CrossReplicaState: the EF residual
    is sharded over ``axis`` (one slice per shard), everything else
    replicated."""
    return CrossReplicaState(inner=P(), ef=EFState(residual=P(axis)),
                             shard_grad_norm=P(), ef_err_norm=P())


def compress_metrics(opt_state) -> dict:
    """Compression-health scalars from any pytree holding CrossReplicaState
    nodes: residual norm (summed in quadrature over multiple optimizers) and
    max pre-reduce shard grad norm.  {} when nothing is compressed."""
    states = [s for s in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda x: isinstance(x, CrossReplicaState))
        if isinstance(s, CrossReplicaState)]
    if not states:
        return {}
    err = jnp.sqrt(sum(jnp.square(s.ef_err_norm) for s in states))
    shard = jnp.max(jnp.stack([s.shard_grad_norm for s in states]))
    return {"compress_err_norm": err, "grad_norm_shard_max": shard}


def soft_update(target, online, tau: float):
    """Polyak averaging for target networks (DDPG/TD3/SAC)."""
    return jax.tree_util.tree_map(
        lambda t, o: (1 - tau) * t.astype(F32) + tau * o.astype(F32), target, online)
