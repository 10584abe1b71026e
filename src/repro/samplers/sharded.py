"""Sharded sampler — the paper's Parallel-CPU/GPU workers as SPMD shards.

rlpyt forks worker processes and synchronizes per batch (CPU) or per step
(GPU).  Under SPMD there are no processes: ``shard_map`` over the 'data' mesh
axis gives each device its own env shard stepping locally, with action
selection per shard (Parallel-CPU analogue: model replicated, envs local).
Collectives appear only for the psum'd trajectory stats — mirroring
"synchronization across workers only per sampling batch" (paper §2.1).

Two entry points:
- ``collect``       — standalone shard_map'd rollout returning the global
                      (T, B) batch; what non-mesh runners call.
- ``local_collect`` — the shard-local body, for callers that are ALREADY
                      inside a ``shard_map`` over ``self.axis`` (the SPMD
                      TrainLoop fuses it with insert/sample/update so the
                      whole log window is one sharded program).
"""
from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .serial import SerialSampler, SamplerState

F32 = jnp.float32

_SCALAR_STATS = ("completed_return_sum", "completed_len_sum", "completed_count")


class ShardedSampler:
    """n_envs TOTAL envs sharded over ``axis`` of ``mesh``.  Same interface as
    SerialSampler; collect() is a shard_map'd per-device serial rollout."""

    def __init__(self, env_spec, agent, n_envs: int, horizon: int, *,
                 mesh: Mesh, axis: str = "data"):
        self.env = env_spec
        self.agent = agent
        self.n_envs = n_envs
        self.horizon = horizon
        self.mesh = mesh
        self.axis = axis
        n_shards = mesh.shape[axis]
        assert n_envs % n_shards == 0, (n_envs, n_shards)
        self.n_shards = n_shards
        self._local = SerialSampler(env_spec, agent, n_envs // n_shards, horizon)
        self._global = SerialSampler(env_spec, agent, n_envs, horizon)

    def init(self, rng, agent_state_kwargs=None) -> SamplerState:
        return self._global.init(rng, agent_state_kwargs)

    def state_spec(self, state: SamplerState) -> SamplerState:
        """PartitionSpec tree for the GLOBAL state: per-env leaves sharded
        over ``axis``, rng + psum'd episode scalars replicated.  This is the
        in/out spec any enclosing shard_map must use for the sampler state."""
        fields = {}
        for name in SamplerState._fields:
            leaf_tree = getattr(state, name)
            if name in _SCALAR_STATS or name == "rng":
                fields[name] = jax.tree_util.tree_map(lambda _: P(), leaf_tree)
            else:
                fields[name] = jax.tree_util.tree_map(
                    lambda l: P(self.axis) if (hasattr(l, "ndim") and l.ndim >= 1)
                    else P(), leaf_tree)
        return SamplerState(**fields)

    # kept for callers of the original private name
    _state_spec = state_spec

    def local_collect(self, params, state: SamplerState):
        """Shard-local rollout; MUST run inside shard_map over ``self.axis``.

        ``state`` is the local block of a state partitioned by
        ``state_spec``: per-env leaves are the shard's slice, rng and episode
        scalars replicated.  Shards decorrelate by folding the axis index
        into the replicated key; episode stats are psum'd back to replicated
        so ``traj_stats``/``reset_stats`` behave exactly as in serial.
        Returns (local state', local (T, B/n_shards) batch).
        """
        axis = self.axis
        my = jax.random.fold_in(state.rng, jax.lax.axis_index(axis))
        nxt = jax.random.fold_in(state.rng, 0x5EED)
        s2, batch = self._local.collect(params, state._replace(rng=my))
        s2 = s2._replace(
            rng=nxt,
            completed_return_sum=jax.lax.psum(
                s2.completed_return_sum - state.completed_return_sum, axis)
            + state.completed_return_sum,
            completed_len_sum=jax.lax.psum(
                s2.completed_len_sum - state.completed_len_sum, axis)
            + state.completed_len_sum,
            completed_count=jax.lax.psum(
                s2.completed_count - state.completed_count, axis)
            + state.completed_count,
        )
        return s2, batch

    def local_bootstrap(self, params, state: SamplerState):
        """Shard-local bootstrap values (B/n_shards,); shard_map context only."""
        return self._local.bootstrap_value(params, state)

    def collect(self, params, state: SamplerState):
        axis = self.axis
        state_spec = self.state_spec(state)
        params_spec = jax.tree_util.tree_map(lambda _: P(), params)
        out_shapes = jax.eval_shape(
            lambda p, s: self._local.collect(p, s._replace(rng=s.rng)), params,
            jax.tree_util.tree_map(
                lambda l, sp: l if sp == P() or not hasattr(l, "shape")
                else jax.ShapeDtypeStruct((l.shape[0] // self.n_shards,) + l.shape[1:],
                                          l.dtype),
                state, state_spec))
        batch_spec = jax.tree_util.tree_map(
            lambda l: P(None, axis) if l.ndim >= 2 else P(None), out_shapes[1])

        f = jax.shard_map(self.local_collect, mesh=self.mesh,
                          in_specs=(params_spec, state_spec),
                          out_specs=(state_spec, batch_spec),
                          check_vma=False)
        return f(params, state)

    def bootstrap_value(self, params, state: SamplerState):
        return self._global.bootstrap_value(params, state)

    traj_stats = staticmethod(SerialSampler.traj_stats)
    reset_stats = staticmethod(SerialSampler.reset_stats)
