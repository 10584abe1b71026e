"""SlotCache: a slot-indexed KV/state cache over ``models/backbones.py``.

The continuous-batching engine keeps ONE batch cache of ``n_slots``
sequences alive forever; requests come and go by *slot surgery*, never by
reshaping the batch — that is what keeps the jitted decode program
shape-stable (zero recompilation) while the traffic is ragged:

- ``write_prefill_at(slot, prompt)``: run a **single-prompt** jitted
  prefill at the largest *bucket* length <= prompt_len (one compiled
  program per bucket, warmed up front), teacher-force the remaining
  prompt tail through the single-slot decode program (exact for every
  family — attention KV, rolling-window rings, and Mamba-2 recurrent
  state all advance by the same recurrence decode uses), then copy the
  whole (1,)-batch cache into the batch cache at ``slot`` with one jitted
  ``dynamic_update_index_in_dim`` tree write.  Because the source cache is
  freshly initialized inside the prefill program, the write overwrites
  EVERY position of the slot — a reused slot is bit-identical to a fresh
  one (``tests/test_serving.py``).
- ``reset_slot(slot)``: zero the slot (length and contents).  Retirement
  hygiene only — correctness never depends on it, since raggedness is
  masked by per-slot ``cache["lengths"]`` / per-batch ``kv_len`` in
  ``attention_decode`` and reuse rewrites the slot wholesale.

Ring-window layers need no special casing: the rolling layout ("absolute
position p lives at index p % S") is T-independent, so a single-prompt
prefill + tail advance lays the ring out exactly as a batched prefill
would.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..models import backbones as bb
from ..models.config import ModelConfig
from ..telemetry.trace import get_tracer

F32 = jnp.float32

DEFAULT_BUCKETS = (8, 16, 24, 32, 48, 64)


def bucket_for(prompt_len: int, buckets: Sequence[int]) -> int:
    """Largest bucket <= prompt_len (prefill never sees pad tokens — pads
    would corrupt recurrent-state families; the tail is advanced exactly)."""
    fit = [b for b in buckets if b <= prompt_len]
    if not fit:
        raise ValueError(f"prompt_len {prompt_len} below smallest bucket "
                         f"{min(buckets)}")
    return max(fit)


def _family_extras(cfg: ModelConfig, batch: int) -> Dict[str, jnp.ndarray]:
    kw = {}
    if cfg.family == "vlm":
        kw["img"] = jnp.zeros((batch, cfg.n_img_tokens, cfg.d_model),
                              jnp.bfloat16)
    if cfg.family == "encdec":
        kw["enc_frames"] = jnp.zeros((batch, cfg.enc_len, cfg.d_model),
                                     jnp.bfloat16)
    return kw


def _write_slot(cache, logits, cache1, logits1, slot):
    """Copy the (1,)-batch cache/logits into batch position ``slot``.
    Cache leaves carry batch at axis 1 ((n_sb, B, ...)), ``lengths`` at
    axis 0."""
    def w(dst, src):
        axis = 0 if dst.ndim == 1 else 1
        return jax.lax.dynamic_update_index_in_dim(
            dst, jnp.squeeze(src, axis).astype(dst.dtype), slot, axis)

    new_cache = jax.tree_util.tree_map(w, cache, cache1)
    new_logits = jax.lax.dynamic_update_index_in_dim(
        logits, logits1[0].astype(logits.dtype), slot, 0)
    return new_cache, new_logits


def _reset_slot(cache, logits, slot):
    def r(dst):
        axis = 0 if dst.ndim == 1 else 1
        return jax.lax.dynamic_update_index_in_dim(
            dst, jnp.zeros(dst.shape[:axis] + dst.shape[axis + 1:],
                           dst.dtype), slot, axis)

    return (jax.tree_util.tree_map(r, cache),
            jax.lax.dynamic_update_index_in_dim(
                logits, jnp.zeros(logits.shape[1:], logits.dtype), slot, 0))


class SlotCache:
    """Batch cache + the jitted slot-surgery programs for one config."""

    def __init__(self, cfg: ModelConfig, n_slots: int, max_context: int, *,
                 buckets: Sequence[int] = DEFAULT_BUCKETS):
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_context = max_context
        self.buckets = tuple(sorted(set(buckets)))
        self.cache = None
        self.logits = None
        self.reset_all()

        cfg_ = cfg
        S = max_context

        def prefill_one(params, prompt):  # prompt: (1, bucket)
            cache1 = bb.init_cache(cfg_, 1, S, img_len=cfg_.n_img_tokens,
                                   enc_len=cfg_.enc_len)
            hidden, cache1 = bb.prefill(params, prompt, cfg_, cache1,
                                        **_family_extras(cfg_, 1))
            logits1 = bb.lm_logits(params, hidden, cfg_)[:, -1].astype(F32)
            return logits1, cache1

        def advance_one(params, cache1, tok):  # tok: (1,) — teacher-forced
            hidden, cache1 = bb.decode_step(params, cache1, tok, cfg_)
            logits1 = bb.lm_logits(params, hidden, cfg_)[:, 0].astype(F32)
            return logits1, cache1

        # One compiled prefill per bucket; everything else compiles once.
        self._prefill = {b: jax.jit(prefill_one) for b in self.buckets}
        self._advance = jax.jit(advance_one)
        # The batch cache and logits are donated: a slot write or reset
        # updates them in place instead of making a second batch cache.
        self._write = jax.jit(_write_slot, donate_argnums=(0, 1))
        self._reset = jax.jit(_reset_slot, donate_argnums=(0, 1))
        self.prefill_tokens = 0  # running count, for prefill tok/s

    # -- lifecycle ------------------------------------------------------------
    def reset_all(self) -> None:
        """Fresh batch cache + logits (programs stay compiled)."""
        self.cache = bb.init_cache(self.cfg, self.n_slots, self.max_context,
                                   img_len=self.cfg.n_img_tokens,
                                   enc_len=self.cfg.enc_len)
        self.logits = jnp.zeros((self.n_slots, self.cfg.padded_vocab), F32)

    def write_prefill_at(self, params, slot: int, prompt: np.ndarray, *,
                         tracer=None) -> None:
        """Prefill ``prompt`` single-sequence and install it at ``slot``;
        its three phases are the spans ``serving.prefill``,
        ``serving.tail_advance`` and ``serving.slot_write`` on ``tracer``
        (the process-global tracer when None)."""
        tr = tracer if tracer is not None else get_tracer()
        plen = int(prompt.shape[0])
        if plen >= self.max_context:
            raise ValueError(f"prompt_len {plen} >= max_context "
                             f"{self.max_context}")
        b = bucket_for(plen, self.buckets)
        with tr.span("serving.prefill", bucket=b):
            tokens = jnp.asarray(prompt[None, :b], jnp.int32)
            logits1, cache1 = self._prefill[b](params, tokens)
        with tr.span("serving.tail_advance", steps=plen - b):
            for i in range(b, plen):  # exact tail advance, shape-stable (B=1)
                logits1, cache1 = self._advance(
                    params, cache1, jnp.asarray(prompt[i:i + 1], jnp.int32))
        with tr.span("serving.slot_write"):
            self.cache, self.logits = self._write(self.cache, self.logits,
                                                  cache1, logits1, slot)
        self.prefill_tokens += plen

    def reset_slot(self, slot: int) -> None:
        self.cache, self.logits = self._reset(self.cache, self.logits, slot)

    def lengths(self) -> np.ndarray:
        return np.asarray(self.cache["lengths"])

    # recompile-detector hooks: name -> jitted callable
    def jitted_programs(self) -> Dict[str, object]:
        out = {f"serving.prefill_b{b}": f for b, f in self._prefill.items()}
        out["serving.advance"] = self._advance
        out["serving.write_slot"] = self._write
        out["serving.reset_slot"] = self._reset
        return out

    def warmup(self, params) -> None:
        """Compile every bucket prefill + the surgery programs up front so
        steady-state serving never compiles (the zero-recompile invariant).
        The writes update the batch cache in place, so warm-up ends with a
        fresh one."""
        keep_count = self.prefill_tokens
        for i, b in enumerate(self.buckets):
            # smallest bucket warms the tail-advance program too (len b+1)
            dummy = np.zeros((b + 1 if i == 0 else b,), np.int32)
            self.write_prefill_at(params, 0, dummy)
        self.reset_slot(0)
        self.reset_all()
        self.prefill_tokens = keep_count
