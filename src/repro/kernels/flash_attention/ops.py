"""Public flash-attention ops behind the kernel backend registry.

``flash_attention`` is the differentiable train/prefill op: forward is the
Pallas kernel (interpret or compiled per the registry), backward is a
``custom_vjp`` through the reference math — the standard forward-optimized
kernel + XLA-backward split, so the fused PPO/A2C update compiles through
the kernel unchanged.  ``flash_attention_decode`` is the KV-cache decode op
(one query token against a partially-filled cache, per-sequence ``kv_len``);
the decode path never needs gradients.

The ``interpret`` default is derived from the registry (None -> interpret
everywhere except a resolved ``pallas`` backend) instead of the old
hard-coded True, which silently shipped interpret mode to compiled
backends.  Resolution happens OUTSIDE the jit boundary so flipping the
backend never reuses a stale trace.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .. import registry
from .flash_attention import flash_attention_pallas
from .ref import attention_reference


def _row_block(T: int, G: int, block_q: int) -> int:
    """Rows per block of the (G*T, dh) head-group matrix: ``block_q`` when
    T >= block_q (T is padded to a multiple), else whole heads — T times the
    largest divisor of G that keeps the block within ``block_q`` rows."""
    if T >= block_q:
        return block_q
    gq = max(d for d in range(1, G + 1) if G % d == 0 and d * T <= block_q)
    return gq * T


def _pad_to(x, axis, mult):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), n


@functools.partial(jax.jit, static_argnums=(3,))
def _fa_impl(q, k, v, opts):
    """Pad ragged shapes to block multiples and run the kernel.
    opts = (causal, window, softcap, q_offset, block_q, block_k, interpret).

    Handles non-multiple T/S by padding (padded K positions are masked out
    by the causal/validity logic: they sit at positions >= S, beyond any
    real query when q_offset + T <= S)."""
    causal, window, softcap, q_offset, block_q, block_k, interpret = opts
    B, T, H, dh = q.shape
    S = k.shape[1]
    bq = _row_block(T, H // k.shape[2], block_q)
    bk = min(block_k, max(S, 1))
    qp, T0 = _pad_to(q, 1, bq if T >= block_q else 1)
    kp, S0 = _pad_to(k, 1, bk)
    vp, _ = _pad_to(v, 1, bk)
    if not causal and S0 != kp.shape[1]:
        # non-causal padding needs explicit masking; fall back to reference
        return attention_reference(q, k, v, causal=causal, window=window,
                                   softcap=softcap, q_offset=q_offset)
    out = flash_attention_pallas(qp, kp, vp, causal=causal, window=window,
                                 softcap=softcap, q_offset=q_offset,
                                 block_q=bq, block_k=bk, interpret=interpret)
    return out[:, :T0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _fa(q, k, v, opts):
    return _fa_impl(q, k, v, opts)


def _fa_fwd(q, k, v, opts):
    return _fa_impl(q, k, v, opts), (q, k, v)


def _fa_bwd(opts, res, g):
    # Backward through the O(T*chunk) reference math: the kernel win is the
    # forward's removed score traffic; the backward recomputes from the
    # saved (q, k, v) residuals and lets XLA differentiate the oracle.
    causal, window, softcap, q_offset = opts[:4]
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: attention_reference(
            q_, k_, v_, causal=causal, window=window, softcap=softcap,
            q_offset=q_offset),
        q, k, v)
    return vjp(g)


_fa.defvjp(_fa_fwd, _fa_bwd)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    q_offset: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None):
    """Fused GQA attention. q:(B,T,H,dh), k/v:(B,S,Hkv,dh) -> (B,T,H,dh).
    Differentiable (custom_vjp; backward via the reference oracle)."""
    interpret = registry.resolve_interpret("attention", interpret)
    opts = (causal, window, softcap, q_offset, block_q, block_k, interpret)
    return _fa(q, k, v, opts)


@functools.partial(jax.jit, static_argnums=(4,))
def _fa_decode_impl(q, k, v, kv_len, opts):
    softcap, block_q, block_k, interpret = opts
    B, T, H, dh = q.shape
    bk = min(block_k, max(k.shape[1], 1))
    kp, _ = _pad_to(k, 1, bk)
    vp, _ = _pad_to(v, 1, bk)
    # padded slots sit at positions >= S >= max(kv_len): masked by kv_len
    bq = _row_block(T, H // k.shape[2], block_q)
    qp, T0 = _pad_to(q, 1, bq if T >= block_q else 1)
    out = flash_attention_pallas(qp, kp, vp, causal=False, window=None,
                                 softcap=softcap, kv_len=kv_len,
                                 block_q=bq, block_k=bk, interpret=interpret)
    return out[:, :T0]


def flash_attention_decode(q, k, v, kv_len, *,
                           softcap: Optional[float] = None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: Optional[bool] = None):
    """Decode attention against a KV cache.  q:(B,T,H,dh) (T is 1 in the
    serving loop), k/v:(B,S,Hkv,dh), kv_len:(B,) valid slots per sequence.
    Ring-buffer (sliding-window) caches pass kv_len=min(len+1, S): slot
    order carries no positional meaning, so validity is the whole mask."""
    interpret = registry.resolve_interpret("attention", interpret)
    return _fa_decode_impl(q, k, v, jnp.asarray(kv_len, jnp.int32),
                           (softcap, block_q, block_k, interpret))
