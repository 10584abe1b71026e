"""Scan-fused training loop — one compiled program per log window.

The synchronous runners used to dispatch one jitted program per iteration
and return metrics to the host every time.  TrainLoop instead compiles
``log_interval`` iterations of (collect -> [insert -> sample -> update^k])
into ONE ``lax.scan``-over-iterations program; per-iteration metrics come
back stacked, and the host touches device data only at log/checkpoint
boundaries.  Amortizing dispatch across the fused window is the ROADMAP
"fast as the hardware allows" direction — fewer host<->device round trips,
and XLA sees the whole window at once.

The loop is algorithm-agnostic: it consumes the algorithm's declarative
``BatchSpec`` (core/batch_spec.py) through ``make_algo_batch`` and a
``ReplayLike`` backend (replay/interface.py), so all three families —
deep Q-learning, policy gradients, Q-value policy gradients — run through
the same code path, the paper's shared-infrastructure thesis made literal.

``fuse=False`` keeps the per-iteration dispatch behavior (one jitted call
per iteration) — the baseline benchmarks/bench_learning.py compares against.

SPMD data parallelism (paper §2.4 synchronous multi-GPU RL)
-----------------------------------------------------------
Passing ``mesh=``/``axis=`` turns the SAME fused window into one
``shard_map``'d program over the data axis: each device steps its env shard
(ShardedSampler.local_collect), inserts into and samples from its OWN slice
of the device replay (DeviceReplay.init_sharded), and computes gradients on
its local batch; the only cross-device traffic is the pmean of gradients
(``train.optim.cross_replica`` wraps every Optimizer the algorithm holds),
the psum'd episode stats, and the gathered metrics.  Params and optimizer
state stay replicated, so the sharded update IS the serial update on the
concatenated batch — rlpyt's "replicated model, all-reduced gradients",
compiled instead of spawned.

Periodic offline evaluation (paper §2.1) plugs in at log boundaries: pass
``eval_sampler=`` to ``drive`` (or the runner shells) and eval metrics are
reported through the Logger alongside training stats.
"""
from __future__ import annotations

import copy
import time
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.batch_spec import make_algo_batch
from ..replay.interface import ReplayLike
from ..telemetry import sentinels as sentinels_mod
from ..telemetry import trace
from ..telemetry.sentinels import NonFiniteError
from ..train.checkpoint import save_checkpoint
from ..train.optim import (Optimizer, CrossReplicaState, compress_metrics,
                           cross_replica, cross_replica_specs)
from ..utils.logger import Logger


@partial(jax.jit, static_argnums=1)
def split_keys(rng, n: int):
    """n sequential (rng, k) splits as ONE compiled scan — the same key
    stream as one-split-per-iteration in the unfused loop, so fused and
    unfused runs see identical keys, without n host dispatches."""
    def body(r, _):
        r, k = jax.random.split(r)
        return r, k
    return jax.lax.scan(body, rng, None, length=n)


def last_of(stacked):
    return jax.tree_util.tree_map(lambda x: x[-1], stacked)


class TrainLoop:
    """Unified synchronous loop over sampler + algo (+ device replay).

    On-policy (spec.mode == "rollout"):  collect -> update.
    Replayed  (spec.mode == "transition"): collect -> insert -> k x
    (sample -> update -> priority update), all inside the fused window.

    With ``mesh``/``axis`` the window is shard_map'd over the data axis
    (see module docstring); the sampler must then be a ShardedSampler (or
    expose the same ``local_collect``/``state_spec`` surface) on the same
    mesh axis, and replayed algorithms shard both the replay state and the
    sample batch (each shard draws batch_size / n_shards).
    """

    def __init__(self, sampler, algo, *, replay: Optional[ReplayLike] = None,
                 batch_size: Optional[int] = None,
                 updates_per_collect: int = 1, fuse: bool = True,
                 mesh=None, axis: str = "data",
                 compress: Optional[str] = None,
                 sentinels: bool = False, nan_guard: bool = False):
        spec = algo.batch_spec
        if spec is None:
            raise ValueError(f"{type(algo).__name__} declares no BatchSpec")
        if spec.mode == "sequence":
            raise ValueError("sequence-mode algorithms (R2D1) need the host "
                             "sequence replay — use AsyncR2D1Runner")
        if spec.replayed:
            if replay is None or not replay.device_resident:
                raise ValueError("replayed algorithms need a device-resident "
                                 "ReplayLike (see AsyncRunner for host replay)")
            if batch_size is None:
                raise ValueError("replayed algorithms need batch_size")
        self.sampler, self.algo, self.spec = sampler, algo, spec
        self.replay = replay
        self.batch_size = batch_size
        self.k = updates_per_collect
        self.fuse = fuse
        self.mesh, self.axis = mesh, axis
        self.compress = compress
        if compress and mesh is None:
            raise ValueError("compress= needs a mesh (the compressed stage "
                             "is the data-axis gradient all-reduce)")
        # in-program telemetry: sentinels ride the scan as extra stacked ys;
        # nan_guard implies them (the guard reads the nonfinite channel)
        self.nan_guard = nan_guard
        self.sentinels_on = sentinels or nan_guard
        self.tracer = trace.get_tracer()
        if mesh is not None:
            if not hasattr(sampler, "local_collect"):
                raise ValueError("mesh mode needs a sharded sampler exposing "
                                 "local_collect/state_spec (ShardedSampler)")
            if getattr(sampler, "axis", axis) != axis:
                raise ValueError(f"sampler shards over {sampler.axis!r} but "
                                 f"TrainLoop was given axis={axis!r}")
            self.n_shards = mesh.shape[axis]
            if spec.replayed:
                if batch_size % self.n_shards:
                    raise ValueError(f"batch_size {batch_size} not divisible "
                                     f"by {self.n_shards} shards")
                self._local_batch = batch_size // self.n_shards
            # the psum seam: every Optimizer the algorithm holds pmeans
            # grads over the data axis before stepping, so params/opt state
            # stay replicated and the update equals the global-batch update —
            # no algorithm changes its ``update``.  Wrap on a shallow copy:
            # the caller's algo must stay usable outside this mesh (a pmean
            # traced outside shard_map fails on the unbound axis name).
            self.algo = algo = copy.copy(algo)
            for name, val in list(vars(algo).items()):
                if isinstance(val, Optimizer):
                    setattr(algo, name, cross_replica(
                        val, axis, compress=compress,
                        ef_shards=self.n_shards))
        self._step = jax.jit(self._iteration)
        self._window = jax.jit(self._window_impl)
        # recompilation detector: every jitted entry point is watched; the
        # host driver polls trace-cache growth at boundaries (a silently
        # retracing window is the classic fused-loop perf killer)
        self.tracer.watch_jit("train_loop.step", self._step)
        self.tracer.watch_jit("train_loop.window", self._window)
        # sharded programs are built lazily — their PartitionSpec trees need
        # the actual state pytrees, which exist only once init() has run.
        self._sharded_window = None
        self._sharded_ci = None
        # ONE jitted collect+insert, shared by warmup and (via the traced
        # impl) every fused iteration — no per-pass re-jit.
        if mesh is None:
            self.collect_insert = jax.jit(self._collect_insert_impl)
            self.tracer.watch_jit("train_loop.collect_insert",
                                  self.collect_insert)
        else:
            self.collect_insert = self._sharded_collect_insert

    # -- pure bodies (traced by both the fused and per-iteration paths) -----
    def _collect_insert_impl(self, params, sampler_state, replay_state):
        sampler_state, batch = self.sampler.collect(params, sampler_state)
        replay_state = self.replay.insert(replay_state, batch)
        return sampler_state, replay_state

    def _sentinels(self, prev_params, train_state, info, replay_state,
                   env_steps: int):
        """One iteration's Sentinels pytree, or None when disabled — pure
        reads over already-live values, so enabling them never perturbs the
        parameter math (bit-identity pinned in tests/test_telemetry.py)."""
        if not self.sentinels_on:
            return None
        cm = compress_metrics(train_state.opt_state)
        return sentinels_mod.compute(prev_params, train_state.params,
                                     info.loss, info.grad_norm, replay_state,
                                     env_steps,
                                     compress_err_norm=cm.get(
                                         "compress_err_norm"),
                                     grad_norm_shard_max=cm.get(
                                         "grad_norm_shard_max"))

    def _iteration(self, train_state, sampler_state, replay_state, rng):
        prev_params = train_state.params
        env_steps = self.sampler.horizon * self.sampler.n_envs
        if self.spec.on_policy:
            sampler_state, batch = self.sampler.collect(train_state.params,
                                                        sampler_state)
            bootstrap = self.sampler.bootstrap_value(train_state.params,
                                                     sampler_state)
            algo_batch = make_algo_batch(self.spec, batch,
                                         {"bootstrap_value": bootstrap})
            train_state, info = self.algo.update(train_state, algo_batch, rng)
            sent = self._sentinels(prev_params, train_state, info, None,
                                   env_steps)
            return train_state, sampler_state, replay_state, info, sent

        sampler_state, replay_state = self._collect_insert_impl(
            train_state.params, sampler_state, replay_state)

        def do_update(carry, k_up):
            ts, rs = carry
            k_s, k_u = jax.random.split(k_up)
            mb, idx, w = self.replay.sample(rs, k_s, self.batch_size)
            algo_batch = make_algo_batch(self.spec, mb, {"is_weights": w})
            ts, info = self.algo.update(ts, algo_batch, k_u)
            rs = self.replay.update_priorities(
                rs, idx, *(info.extra[k] for k in self.spec.priority_keys))
            return (ts, rs), info

        ks = jax.random.split(rng, self.k)
        (train_state, replay_state), infos = jax.lax.scan(
            do_update, (train_state, replay_state), ks)
        info = last_of(infos)
        sent = self._sentinels(prev_params, train_state, info, replay_state,
                               env_steps)
        return train_state, sampler_state, replay_state, info, sent

    def _window_impl(self, train_state, sampler_state, replay_state, keys):
        def body(carry, k):
            ts, ss, rs = carry
            ts, ss, rs, info, sent = self._iteration(ts, ss, rs, k)
            return (ts, ss, rs), (info, sent)

        (ts, ss, rs), (infos, sents) = jax.lax.scan(
            body, (train_state, sampler_state, replay_state), keys)
        return ts, ss, rs, infos, sents

    # -- SPMD bodies (run INSIDE shard_map over self.axis) -------------------
    def _replicate_info(self, info):
        """Make the per-iteration OptInfo replicated: scalar leaves (losses,
        means over the local batch) pmean to their global-batch value;
        batch-leading leaves (per-sample td_abs) all-gather to full width."""
        ax = self.axis

        def rep(x):
            x = jnp.asarray(x)
            if x.ndim == 0:
                return jax.lax.pmean(x, ax)
            return jax.lax.all_gather(x, ax, axis=0, tiled=True)

        return jax.tree_util.tree_map(rep, info)

    def _sentinels_local(self, prev_params, train_state, info, replay_state):
        """Shard-local sentinels -> replicated global values (psum/pmean/
        pmax per field; see telemetry/sentinels.py replicate)."""
        if not self.sentinels_on:
            return None
        local_steps = self.sampler.horizon * self.sampler.n_envs \
            // self.n_shards
        cm = compress_metrics(train_state.opt_state)
        sent = sentinels_mod.compute(prev_params, train_state.params,
                                     info.loss, info.grad_norm, replay_state,
                                     local_steps,
                                     compress_err_norm=cm.get(
                                         "compress_err_norm"),
                                     grad_norm_shard_max=cm.get(
                                         "grad_norm_shard_max"))
        return sentinels_mod.replicate(sent, self.axis)

    def _iteration_local(self, train_state, sampler_state, replay_state, rng):
        prev_params = train_state.params
        if self.spec.on_policy:
            sampler_state, batch = self.sampler.local_collect(
                train_state.params, sampler_state)
            bootstrap = self.sampler.local_bootstrap(train_state.params,
                                                     sampler_state)
            algo_batch = make_algo_batch(self.spec, batch,
                                         {"bootstrap_value": bootstrap})
            train_state, info = self.algo.update(train_state, algo_batch, rng)
            info = self._replicate_info(info)
            return (train_state, sampler_state, replay_state, info,
                    self._sentinels_local(prev_params, train_state, info,
                                          None))

        sampler_state, batch = self.sampler.local_collect(train_state.params,
                                                          sampler_state)
        replay_state = self.replay.insert(replay_state, batch)
        shard = jax.lax.axis_index(self.axis)

        def do_update(carry, k_up):
            ts, rs = carry
            k_s, k_u = jax.random.split(k_up)
            # decorrelate replay draws across shards; the update key stays
            # replicated so replicated computations stay replicated
            mb, idx, w = self.replay.sample(rs, jax.random.fold_in(k_s, shard),
                                            self._local_batch)
            algo_batch = make_algo_batch(self.spec, mb, {"is_weights": w})
            ts, info = self.algo.update(ts, algo_batch, k_u)
            rs = self.replay.update_priorities(
                rs, idx, *(info.extra[k] for k in self.spec.priority_keys))
            return (ts, rs), info

        ks = jax.random.split(rng, self.k)
        (train_state, replay_state), infos = jax.lax.scan(
            do_update, (train_state, replay_state), ks)
        info = self._replicate_info(last_of(infos))
        return (train_state, sampler_state, replay_state, info,
                self._sentinels_local(prev_params, train_state, info,
                                      replay_state))

    def _sharded_window_impl(self, train_state, sampler_state, replay_state,
                             keys):
        if replay_state is not None:
            replay_state = self.replay.local_view(replay_state)

        def body(carry, k):
            ts, ss, rs = carry
            ts, ss, rs, info, sent = self._iteration_local(ts, ss, rs, k)
            return (ts, ss, rs), (info, sent)

        (ts, ss, rs), (infos, sents) = jax.lax.scan(
            body, (train_state, sampler_state, replay_state), keys)
        if rs is not None:
            rs = self.replay.merge_view(rs)
        return ts, ss, rs, infos, sents

    def _train_state_spec(self, train_state):
        """shard_map spec for the train state: P() (replicated) everywhere,
        except compressed optimizers' EF residuals, which are sharded over
        the data axis (each shard carries its own quantization error)."""
        if not self.compress:
            return P()
        is_crs = lambda x: isinstance(x, CrossReplicaState)
        spec = jax.tree_util.tree_map(
            lambda x: cross_replica_specs(self.axis) if is_crs(x) else P(),
            train_state, is_leaf=is_crs)
        if not any(is_crs(x) for x in jax.tree_util.tree_leaves(
                train_state, is_leaf=is_crs)):
            raise ValueError(
                "compress= is set but the train state carries no error-"
                "feedback residual — initialize it through the loop's "
                "wrapped algo: loop.algo.init_train_state(...)")
        return spec

    def _build_sharded(self, train_state, sampler_state, replay_state):
        ss_spec = self.sampler.state_spec(sampler_state)
        ts_spec = self._train_state_spec(train_state)
        if self.spec.on_policy:
            def window(ts, ss, keys):
                ts, ss, _, infos, sents = self._sharded_window_impl(
                    ts, ss, None, keys)
                return ts, ss, infos, sents
            f = jax.shard_map(window, mesh=self.mesh,
                              in_specs=(ts_spec, ss_spec, P()),
                              out_specs=(ts_spec, ss_spec, P(), P()),
                              check_vma=False)
        else:
            rs_spec = self.replay.shard_spec(self.axis)

            def window(ts, ss, rs, keys):
                return self._sharded_window_impl(ts, ss, rs, keys)
            f = jax.shard_map(window, mesh=self.mesh,
                              in_specs=(ts_spec, ss_spec, rs_spec, P()),
                              out_specs=(ts_spec, ss_spec, rs_spec, P(), P()),
                              check_vma=False)
        self._sharded_window = jax.jit(f)
        self.tracer.watch_jit("train_loop.sharded_window",
                              self._sharded_window)

    def _call_sharded(self, train_state, sampler_state, replay_state, keys):
        if self._sharded_window is None:
            self._build_sharded(train_state, sampler_state, replay_state)
        if self.spec.on_policy:
            ts, ss, infos, sents = self._sharded_window(
                train_state, sampler_state, keys)
            return ts, ss, None, infos, sents
        return self._sharded_window(train_state, sampler_state, replay_state,
                                    keys)

    def _sharded_collect_insert(self, params, sampler_state, replay_state):
        if self._sharded_ci is None:
            ss_spec = self.sampler.state_spec(sampler_state)
            rs_spec = self.replay.shard_spec(self.axis)

            def body(params, ss, rs):
                ss, batch = self.sampler.local_collect(params, ss)
                rs = self.replay.merge_view(
                    self.replay.insert(self.replay.local_view(rs), batch))
                return ss, rs
            self._sharded_ci = jax.jit(jax.shard_map(
                body, mesh=self.mesh, in_specs=(P(), ss_spec, rs_spec),
                out_specs=(ss_spec, rs_spec), check_vma=False))
        return self._sharded_ci(params, sampler_state, replay_state)

    # -- host drivers --------------------------------------------------------
    @staticmethod
    def _stack(items):
        if items and items[0] is None:
            return None
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *items)

    def run_window(self, train_state, sampler_state, replay_state, keys):
        """Run len(keys) iterations; returns (ts, ss, rs, stacked infos,
        stacked sentinels-or-None).  Fused: one device program (shard_map'd
        over the data axis in mesh mode).  Unfused: one dispatch per
        iteration."""
        if self.mesh is not None:
            if self.fuse:
                return self._call_sharded(train_state, sampler_state,
                                          replay_state, keys)
            infos, sents = [], []
            for i in range(keys.shape[0]):
                train_state, sampler_state, replay_state, info, sent = \
                    self._call_sharded(train_state, sampler_state,
                                       replay_state, keys[i:i + 1])
                infos.append(last_of(info))
                sents.append(last_of(sent) if sent is not None else None)
            return (train_state, sampler_state, replay_state,
                    self._stack(infos), self._stack(sents))
        if self.fuse:
            return self._window(train_state, sampler_state, replay_state, keys)
        infos, sents = [], []
        for i in range(keys.shape[0]):
            train_state, sampler_state, replay_state, info, sent = self._step(
                train_state, sampler_state, replay_state, keys[i])
            infos.append(info)
            sents.append(sent)
        return (train_state, sampler_state, replay_state,
                self._stack(infos), self._stack(sents))

    def drive(self, rng, train_state, sampler_state, replay_state, *,
              n_iterations: int, log_interval: int, logger: Logger,
              start_iter: int = 0, ckpt_dir: Optional[str] = None,
              ckpt_interval: int = 0,
              ckpt_payload: Optional[Callable] = None,
              eval_sampler=None):
        """Host loop: run windows to the next log/checkpoint boundary, log
        stacked metrics, save, repeat.  Returns (ts, ss, rs, last_info).

        ``eval_sampler`` (samplers/eval.py) triggers an offline evaluation —
        dedicated envs, deterministic agent mode — at every log boundary;
        its metrics land in the same Logger row under an ``eval_`` prefix
        (paper §2.1 offline evaluation at checkpoints).

        Each DISTINCT window length compiles its own fused program (jit
        retraces on the keys' leading shape); misaligned log/ckpt intervals
        cycle through a small fixed set of lengths, so the compile cost is
        bounded by that set, paid once per length."""
        steps_per_iter = self.sampler.horizon * self.sampler.n_envs
        # eval keys come from a forked stream so enabling/disabling eval
        # never perturbs the training keys
        eval_rng = jax.random.fold_in(rng, 0xE7A1)
        tracer = self.tracer
        t0 = time.time()
        since_log = 0
        last_info = None
        last_sents = None
        it = start_iter
        while it < n_iterations:
            boundary = it + log_interval - (it % log_interval)
            if ckpt_dir and ckpt_interval:
                boundary = min(boundary,
                               it + ckpt_interval - (it % ckpt_interval))
            boundary = min(boundary, n_iterations)
            rng, keys = split_keys(rng, boundary - it)
            with tracer.span("collect_train_window", iter_start=it,
                             iters=boundary - it):
                (train_state, sampler_state, replay_state, infos,
                 sents) = self.run_window(train_state, sampler_state,
                                          replay_state, keys)
            last_info = last_of(infos)
            if sents is not None:
                last_sents = sents
                if self.nan_guard:
                    # the ONLY in-window sync: one small stacked channel
                    hit = sentinels_mod.first_nonfinite_iter(sents)
                    if hit is not None:
                        bad_iter, n_bad = it + hit[0], hit[1]
                        tracer.emit("nan_guard", "train_loop",
                                    iteration=bad_iter, n_bad=n_bad)
                        raise NonFiniteError(bad_iter, n_bad)
            since_log += boundary - it
            it = boundary
            if it % log_interval == 0:
                with tracer.span("log_boundary", iteration=it):
                    stats = self.sampler.traj_stats(sampler_state)
                    sampler_state = self.sampler.reset_stats(sampler_state)
                    sps = steps_per_iter * since_log / max(
                        time.time() - t0, 1e-9)
                    extra = {k: v for k, v in last_info.extra.items()
                             if jnp.ndim(v) == 0}
                    row = {"iter": it, "loss": last_info.loss,
                           "grad_norm": last_info.grad_norm,
                           "samples_per_sec": sps, **stats, **extra}
                    if last_sents is not None:
                        row.update(sentinels_mod.summarize(last_sents))
                    if eval_sampler is not None:
                        with tracer.span("eval", iteration=it):
                            em = eval_sampler.run(
                                train_state.params,
                                jax.random.fold_in(eval_rng, it))
                        row.update({f"eval_{k}": v for k, v in em.items()})
                    logger.record(it * steps_per_iter, row)
                tracer.poll_recompiles()
                tracer.memory_snapshot(f"log_boundary_{it}")
                t0, since_log = time.time(), 0
            if ckpt_dir and ckpt_interval and it % ckpt_interval == 0:
                with tracer.span("checkpoint", iteration=it):
                    payload = (train_state if ckpt_payload is None
                               else ckpt_payload(train_state, replay_state))
                    save_checkpoint(ckpt_dir, it, payload,
                                    extra={"iteration": it})
        return train_state, sampler_state, replay_state, last_info
