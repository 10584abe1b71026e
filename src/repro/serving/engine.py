"""In-flight (continuous) batching decode engine.

One jitted decode program steps ALL ``n_slots`` sequences in lockstep; the
host swaps requests in and out of slots *between* dispatches:

    admit: queue -> SlotCache.write_prefill_at(slot)   (bucketed prefill)
    step:  decode_block — ``block`` decode steps compiled as one lax.scan
    retire: slots whose budget hit 0 (or emitted EOS) free up in-scan via
            the carried active mask; the host releases them to the scheduler

Everything the decode program sees is shape-stable — (n_slots,) token
vectors, the fixed batch cache, the active bitmask — so serving ragged
Poisson traffic causes **zero recompilation**: raggedness lives entirely in
``cache["lengths"]`` / ``kv_len`` masking inside ``attention_decode`` and
in the active mask (retired slots keep stepping but are masked out of
sampling and length bumps).

``mode="static"`` runs the SAME programs but only admits when every slot
is free (gang/drain scheduling) — the fixed-batch baseline where the whole
batch decodes until its slowest member finishes.  The two modes therefore
differ *only* in slot swapping, which is exactly what
``benchmarks/bench_serving.py`` isolates.
"""
from __future__ import annotations

import time
from functools import partial
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..models import backbones as bb
from ..models.config import ModelConfig
from ..telemetry.trace import get_tracer
from .scheduler import Scheduler
from .slots import DEFAULT_BUCKETS, SlotCache, bucket_for
from .workload import Request, summarize_requests

F32 = jnp.float32


def make_decode_block(cfg: ModelConfig, block: int, temperature: float,
                      eos_id: Optional[int]):
    """Jitted program: ``block`` decode steps over the whole slot batch.

    Carries (logits, cache, active, remaining); emits per-step tokens and
    the active-at-entry mask so the host can attribute tokens to requests.
    A slot finishes in-scan (budget exhausted or EOS) and stops sampling /
    bumping lengths for the remaining steps of the block.  The logits and
    cache passed in are donated: the block updates them in place, and the
    caller takes the returned ones in their stead.
    """

    def step(params, carry, key):
        logits, cache, active, remaining = carry
        if temperature > 0:
            tok = jax.random.categorical(key, logits / temperature)
        else:
            tok = jnp.argmax(logits, axis=-1)
        tok = jnp.where(active, tok, 0).astype(jnp.int32)
        emitted = active
        hidden, cache = bb.decode_step(params, cache, tok, cfg, active=active)
        logits = bb.lm_logits(params, hidden, cfg)[:, 0].astype(F32)
        remaining = remaining - emitted.astype(jnp.int32)
        done = remaining <= 0
        if eos_id is not None:
            done = done | (tok == eos_id)
        active = active & ~done
        return (logits, cache, active, remaining), (tok, emitted)

    @partial(jax.jit, donate_argnums=(1, 2))
    def decode_block(params, logits, cache, active, remaining, rng):
        (logits, cache, active, remaining), (toks, emitted) = jax.lax.scan(
            lambda c, k: step(params, c, k),
            (logits, cache, active, remaining),
            jax.random.split(rng, block))
        return logits, cache, active, remaining, toks, emitted

    return decode_block


class ContinuousBatchEngine:
    """Slot-based serving engine over one model; run() replays a trace.

    The engine holds the weights as ``bb.compute_weights`` gives them: every
    leaf the forward casts at use is cast once, here, so the bucket
    prefills, the tail advance and the decode block read them in the
    compute dtype and cast nothing on each call (the operands the casts
    gave them).  The number and bytes of the leaves so cast go to the
    global tracer's counters ``serving.precast_leaves`` and
    ``serving.precast_bytes``.  The float32 master copy stays with whoever
    owns it (a trainer); the engine keeps no reference to it.
    """

    def __init__(self, cfg: ModelConfig, params, *, n_slots: int,
                 max_context: int, buckets: Sequence[int] = DEFAULT_BUCKETS,
                 decode_block: int = 4, temperature: float = 0.0,
                 eos_id: Optional[int] = None, max_queue: int = 256,
                 seed: int = 0):
        self.cfg = cfg
        self.params = bb.compute_weights(params, cfg)
        cast = [new for new, old in zip(jax.tree_util.tree_leaves(self.params),
                                        jax.tree_util.tree_leaves(params))
                if new.dtype != old.dtype]
        tr = get_tracer()
        tr.count("serving.precast_leaves", len(cast))
        tr.count("serving.precast_bytes", sum(a.nbytes for a in cast))
        self.n_slots = n_slots
        self.max_queue = max_queue
        self.block = decode_block
        self.seed = seed
        self.slots = SlotCache(cfg, n_slots, max_context, buckets=buckets)
        self._decode_block = make_decode_block(cfg, decode_block, temperature,
                                               eos_id)

    # -- instrumentation ------------------------------------------------------
    def watch(self, tracer) -> None:
        """Register every jitted program with the recompile detector."""
        for name, fn in self.slots.jitted_programs().items():
            tracer.watch_jit(name, fn)
        tracer.watch_jit("serving.decode_block", self._decode_block)

    def warmup(self) -> None:
        """Compile every program (bucket prefills, advance, surgery, decode
        block) before serving, so steady state has zero compiles."""
        self.slots.warmup(self.params)
        rng = jax.random.PRNGKey(self.seed)
        out = self._decode_block(
            self.params, self.slots.logits, self.slots.cache,
            jnp.zeros((self.n_slots,), bool),
            jnp.zeros((self.n_slots,), jnp.int32), rng)
        jax.block_until_ready(out[0])
        self.slots.reset_all()

    # -- the serving loop -----------------------------------------------------
    def run(self, trace: List[Request], *, mode: str = "continuous",
            tracer=None, realtime: bool = True) -> dict:
        """Replay ``trace``; returns the summary metrics row (THE serving
        schema: p50/p99 latency, TTFT, decode_tok_per_sec, ...).

        ``realtime=False`` treats all arrivals as immediate (offline batch)
        — useful for deterministic tests.

        Spans, counters and samples go to ``tracer`` (the process-global
        tracer when None); recompiles are polled only on a given
        ``tracer``.  Every host phase of the loop is a span, so the device's
        idle gaps in a profile fall under the phase that left it idle:
        ``serving.arrivals``, ``serving.admit`` (with the slot cache's
        ``serving.prefill``, ``serving.tail_advance``, ``serving.slot_write``
        and the closing ``serving.admit_wait``), ``serving.decode``,
        ``serving.bookkeeping`` (which also splits the next block's key) and
        ``serving.idle``.  This run alone adds to the counters
        ``serving.admitted`` and ``serving.tail_steps``, and records each request's queue wait
        (admission start, or the run's end for a rejected request, minus
        its arrival) as the sample ``serving.queue_wait_s`` under its rid.
        """
        assert mode in ("continuous", "static")
        tr = tracer if tracer is not None else get_tracer()
        self.slots.reset_all()
        sched = Scheduler(self.n_slots, self.max_queue)
        pending = sorted(trace, key=lambda r: r.arrival_s)
        rejected: List[Request] = []
        slot_req: List[Optional[Request]] = [None] * self.n_slots
        active = np.zeros(self.n_slots, bool)
        remaining = np.zeros(self.n_slots, np.int32)
        rng, k = jax.random.split(jax.random.PRNGKey(self.seed))
        decode_s0 = tr.span_seconds("serving.decode")
        admit_s0 = tr.span_seconds("serving.admit")
        valid_tokens = n_blocks = recompiles = 0
        prefill_tok0 = self.slots.prefill_tokens
        i_next = 0
        if tracer is not None:
            tracer.poll_recompiles()  # baseline: warmup compiles are not
            # steady-state recompiles; anything the in-loop polls catch is.
        t0 = time.perf_counter()

        def now() -> float:
            return time.perf_counter() - t0

        while i_next < len(pending) or sched.n_waiting or active.any():
            # arrivals up to the current clock
            with tr.span("serving.arrivals") as attrs:
                n0 = i_next
                while i_next < len(pending) and (
                        not realtime or pending[i_next].arrival_s <= now()):
                    if not realtime:  # offline batch: whole trace at t=0
                        pending[i_next].arrival_s = 0.0
                    if not sched.submit(pending[i_next]):
                        rejected.append(pending[i_next])
                    i_next += 1
                attrs["n"] = i_next - n0
            # admission: continuous fills any free slot; static only admits
            # into an empty batch (the lockstep fixed-batch baseline)
            if mode == "continuous" or not active.any():
                while (pair := sched.admit()) is not None:
                    req, slot = pair
                    self._admit(tr, req, slot, now())
                    req.t_admitted = now()
                    slot_req[slot] = req
                    active[slot] = True
                    remaining[slot] = req.max_tokens
            if not active.any():
                if i_next < len(pending):  # idle until the next arrival
                    gap = pending[i_next].arrival_s - now()
                    if realtime and gap > 0:
                        with tr.span("serving.idle"):
                            time.sleep(min(gap, 0.02))
                continue

            with tr.span("serving.decode", block_index=n_blocks,
                         n_active=int(active.sum())):
                logits, cache, act_d, rem_d, toks, emitted = \
                    self._decode_block(self.params, self.slots.logits,
                                       self.slots.cache, jnp.asarray(active),
                                       jnp.asarray(remaining), k)
                toks = np.asarray(toks)          # (block, n_slots)
                emitted = np.asarray(emitted)    # (block, n_slots) bool
                new_active = np.array(act_d)   # np.array: views are read-only
                remaining = np.array(rem_d)
            n_blocks += 1
            self.slots.logits, self.slots.cache = logits, cache
            t_block = now()
            with tr.span("serving.bookkeeping") as attrs:
                valid_tokens += int(emitted.sum())
                n_retired = 0
                for s in range(self.n_slots):
                    req = slot_req[s]
                    if req is None:
                        continue
                    out = toks[emitted[:, s], s]
                    if out.size:
                        req.tokens.extend(out.tolist())
                        req.n_generated += int(out.size)
                        if req.t_first_token is None:
                            req.t_first_token = t_block
                    if active[s] and not new_active[s]:  # retired this block
                        req.t_finished = t_block
                        req.tokens = np.asarray(req.tokens, np.int32)
                        tr.observe("serving.queue_wait_s",
                                   req.t_admit_start - req.arrival_s,
                                   key=req.rid)
                        slot_req[s] = None
                        sched.release(s)
                        n_retired += 1
                active = new_active
                if tracer is not None:
                    recompiles += tracer.poll_recompiles()
                rng, k = jax.random.split(rng)   # the next block's key
                attrs["n_retired"] = n_retired

        wall = now()
        for req in rejected:
            tr.observe("serving.queue_wait_s", wall - req.arrival_s,
                       key=req.rid)
        decode_s = tr.span_seconds("serving.decode") - decode_s0
        prefill_s = tr.span_seconds("serving.admit") - admit_s0
        decode_slot_steps = n_blocks * self.block * self.n_slots
        summary = {
            "mode": mode,
            "n_requests": len(trace),
            "n_rejected": sched.n_rejected,
            **summarize_requests(trace),
            "generated_tokens": valid_tokens,
            "decode_tok_per_sec": valid_tokens / max(decode_s, 1e-9),
            "decode_step_ms": decode_s / max(n_blocks * self.block, 1) * 1e3,
            "prefill_tok_per_sec": (self.slots.prefill_tokens - prefill_tok0)
            / max(prefill_s, 1e-9),
            "slot_occupancy": valid_tokens / max(decode_slot_steps, 1),
            "wall_s": wall,
            "recompile_events": recompiles,
        }
        return summary

    def _admit(self, tr, req: Request, slot: int, t: float) -> None:
        """Install ``req`` at ``slot``: its prefill, tail advance and slot
        write, ended by waiting for the slot's logits."""
        plen = req.prompt_len
        bucket = bucket_for(plen, self.slots.buckets)
        req.t_admit_start = t
        req.tokens = []
        tr.count("serving.admitted")
        tr.count("serving.tail_steps", plen - bucket)
        with tr.span("serving.admit", rid=req.rid, slot=slot,
                     prompt_len=plen, bucket=bucket,
                     tail_steps=plen - bucket):
            self.slots.write_prefill_at(self.params, slot, req.prompt,
                                        tracer=tr)
            with tr.span("serving.admit_wait"):
                jax.block_until_ready(self.slots.logits)
