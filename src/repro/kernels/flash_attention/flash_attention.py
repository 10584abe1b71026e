"""Flash attention Pallas TPU kernel: fused blockwise-softmax GQA attention.

TPU adaptation of the FlashAttention idea (the paper's R2D1/serving hot
spot at LM scale): instead of CUDA warps/shared-memory, tiles are BlockSpec
VMEM blocks sized to the MXU (128-multiples); the softmax runs online over
KV tiles with running (max, sum, acc) scratch carried across the minor-most
grid dimension (TPU grids execute sequentially, so VMEM scratch persists).

Layout: the op regroups q as ``(B, Hkv, G*T, dh)`` — the G query heads that
share one KV head stacked along the row axis (row ``g*T + t``) — and k/v as
``(B, Hkv, S, dh)``, then splits rows and KV positions into blocks as a
leading axis.  Every block's last two dims are then a full ``(rows, dh)``
tile, which the TPU compiler requires, GQA never materializes repeated KV
heads, and a decode step (T=1) fills the MXU rows with the whole head group
instead of one query row.

Grid: (B, Hkv, row blocks, KV blocks) — the KV axis iterates innermost.

Supports: causal masking with a query position offset (decode appends),
sliding-window attention (mixtral/gemma2-local), logit softcap (gemma2),
and a per-sequence ``kv_len`` valid-length mask (KV-cache decode: slots
``>= kv_len[b]`` are unwritten and masked out).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
NEG_INF = -1e30


def _attn_kernel(*refs, scale, causal, window, softcap, block_q, block_k,
                 n_kblocks, q_offset, seq_q, has_kvlen):
    if has_kvlen:
        kvl_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
        kvl_ref = None
    b = pl.program_id(0)
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # row r of the block is query token (iq*block_q + r) % seq_q of its head
    if block_q % seq_q == 0:      # the block holds whole heads: every t
        q_lo, q_hi = q_offset, q_offset + seq_q - 1
        rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        qpos = q_offset + jax.lax.rem(rows, seq_q)
    else:                         # seq_q % block_q == 0: one head's slice
        q_lo = q_offset + jax.lax.rem(iq * block_q, seq_q)
        q_hi = q_lo + block_q - 1
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_start = ik * block_k
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    # skip fully-masked tiles (causal: tile entirely in the future;
    # window: tile entirely before the window; kv_len: tile entirely past
    # the sequence's valid cache slots — a traced predicate is fine here)
    run = jnp.asarray(True)
    if causal:
        run &= k_start <= q_hi
    if window is not None:
        run &= k_start + block_k - 1 > q_lo - window
    if kvl_ref is not None:
        run &= k_start < kvl_ref[b]

    @pl.when(run)
    def _tile():
        q = q_ref[0, 0, 0].astype(F32)          # (block_q, dh)
        k = k_ref[0, 0, 0].astype(F32)          # (block_k, dh)
        v = v_ref[0, 0, 0].astype(F32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=F32) * scale
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        mask = jnp.ones_like(s, bool)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        if kvl_ref is not None:
            mask &= kpos < kvl_ref[b]
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                                   # (block_q, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=F32)
        m_scr[...] = m_new

    @pl.when(ik == n_kblocks - 1)
    def _finish():
        safe = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0, 0] = (acc_scr[...] / safe).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           window: Optional[int] = None,
                           softcap: Optional[float] = None,
                           q_offset: int = 0,
                           kv_len=None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = True):
    """q: (B, T, H, dh); k, v: (B, S, Hkv, dh) -> (B, T, H, dh).
    kv_len: optional (B,) int32 — KV slots >= kv_len[b] are masked out
    (decode against a partially-filled cache).

    Row blocks hold ``block_q`` rows of the ``(G*T, dh)`` head-group matrix:
    T must be a multiple of ``block_q`` or ``block_q`` a multiple of T that
    divides G*T; S must be a multiple of ``block_k``."""
    B, T, H, dh = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    R = G * T
    block_k = min(block_k, S)
    assert S % block_k == 0, (S, block_k)
    assert R % block_q == 0 and (T % block_q == 0 or block_q % T == 0), (
        T, G, block_q)
    n_qblocks, n_kblocks = R // block_q, S // block_k
    grid = (B, Hkv, n_qblocks, n_kblocks)
    scale = 1.0 / math.sqrt(dh)

    # (B,T,H,dh) -> (B,Hkv,G,T,dh) -> row blocks (B,Hkv,nq,block_q,dh)
    qg = q.reshape(B, T, Hkv, G, dh).transpose(0, 2, 3, 1, 4)
    qg = qg.reshape(B, Hkv, n_qblocks, block_q, dh)
    kg = k.transpose(0, 2, 1, 3).reshape(B, Hkv, n_kblocks, block_k, dh)
    vg = v.transpose(0, 2, 1, 3).reshape(B, Hkv, n_kblocks, block_k, dh)

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, block_q=block_q, block_k=block_k,
        n_kblocks=n_kblocks, q_offset=q_offset, seq_q=T,
        has_kvlen=kv_len is not None)

    def q_map(b, h, iq, ik, *_):
        return (b, h, iq, 0, 0)

    def kv_map(b, h, iq, ik, *_):
        return (b, h, ik, 0, 0)

    in_specs = [pl.BlockSpec((1, 1, 1, block_q, dh), q_map),
                pl.BlockSpec((1, 1, 1, block_k, dh), kv_map),
                pl.BlockSpec((1, 1, 1, block_k, dh), kv_map)]
    args = [qg, kg, vg]
    n_prefetch = 0
    if kv_len is not None:
        # per-sequence valid lengths ride in SMEM as a scalar prefetch
        args.insert(0, kv_len.astype(jnp.int32))
        n_prefetch = 1

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_prefetch,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, 1, block_q, dh), q_map),
            scratch_shapes=[
                # running max / sum / accumulator, persist across ik
                pltpu.VMEM((block_q, 1), F32),
                pltpu.VMEM((block_q, 1), F32),
                pltpu.VMEM((block_q, dh), F32),
            ]),
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        interpret=interpret,
    )(*args)
    out = out.reshape(B, Hkv, G, T, dh).transpose(0, 3, 1, 2, 4)
    return out.reshape(B, T, H, dh)
