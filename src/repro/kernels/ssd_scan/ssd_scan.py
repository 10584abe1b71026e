"""Mamba2 SSD chunked-scan Pallas TPU kernel.

TPU adaptation of the SSD (state-space duality) algorithm: the GPU version
uses warp-level scans; here each grid step processes one (batch, head,
chunk) tile entirely in VMEM — intra-chunk terms are dense (chunk x chunk)
MXU matmuls, and the inter-chunk recurrence is carried in a VMEM scratch
state across the sequential chunk grid axis.

Layout: the op splits time into chunks as a leading axis and moves heads
before time — x ``(B, H, nC, Q, P)``, B/C ``(B, G, nC, Q, N)``, dt and the
within-chunk cumulative ``dt*A`` ``(B, H, nC, 1, Q)`` (computed by XLA, so
the kernel needs no in-kernel cumsum) — so every block's last two dims are
a full tile the TPU compiler accepts.

Grid: (B, G, nC, heads per group) — heads innermost, so the group's B/C
chunk is fetched once and reused by all of its heads; each head's state
lives in its own slot of a ``(heads per group, N, P)`` scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32


def _col(row, eye):
    """(1, Q) row vector -> (Q, 1) column, via the diagonal (no transpose)."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _ssd_kernel(x_ref, dt_ref, cum_ref, b_ref, c_ref, y_ref, s_final_ref,
                s_scr, *, chunk, n_chunks):
    ic = pl.program_id(2)
    ih = pl.program_id(3)

    @pl.when(ic == 0)
    def _init():
        s_scr[ih] = jnp.zeros(s_scr.shape[1:], F32)

    x = x_ref[0, 0, 0].astype(F32)          # (Q, P)
    dt = dt_ref[0, 0, 0].astype(F32)        # (1, Q)
    cum = cum_ref[0, 0, 0].astype(F32)      # (1, Q) within-chunk cumsum(dt*A)
    Bm = b_ref[0, 0, 0].astype(F32)         # (Q, N)
    Cm = c_ref[0, 0, 0].astype(F32)         # (Q, N)

    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    eye, tri = rows == cols, rows >= cols
    cum_col = _col(cum, eye)                # (Q, 1)
    # intra-chunk decay L[q, k] = exp(cum_q - cum_k) for q >= k
    # (mask BEFORE exp — masked entries are positive and overflow; see ref)
    L = jnp.where(tri, jnp.exp(jnp.where(tri, cum_col - cum, 0.0)), 0.0)
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=F32)   # (Q, K)
    y_diag = jnp.dot(scores * L * dt, x, preferred_element_type=F32)

    s_prev = s_scr[ih]                      # (N, P)
    y_off = jnp.dot(Cm, s_prev, preferred_element_type=F32) * jnp.exp(cum_col)

    cum_last = cum[:, chunk - 1:chunk]      # (1, 1)
    w = _col(jnp.exp(cum_last - cum) * dt, eye)                # (Q, 1)
    s_new = s_prev * jnp.exp(cum_last) + jax.lax.dot_general(
        Bm * w, x, (((0,), (0,)), ((), ())), preferred_element_type=F32)
    s_scr[ih] = s_new

    y_ref[0, 0, 0] = (y_diag + y_off).astype(y_ref.dtype)

    @pl.when(ic == n_chunks - 1)
    def _finish():
        s_final_ref[0, ih] = s_new


def ssd_scan_pallas(x, dt, A, Bmat, Cmat, *, chunk: int = 64,
                    interpret: bool = True):
    """x:(B,T,H,P) dt:(B,T,H) A:(H,) B/C:(B,T,G,N) -> (y (B,T,H,P) in x.dtype,
    final_state (B,H,P,N) f32).  T must be a multiple of ``chunk``."""
    B, T, H, P = x.shape
    G, N = Bmat.shape[2], Bmat.shape[3]
    assert T % chunk == 0, (T, chunk)
    assert H % G == 0, (H, G)
    n_chunks = T // chunk
    hpg = H // G
    grid = (B, G, n_chunks, hpg)

    def heads(a):   # (B, T, H, ...) -> (B, H, nC, Q, ...)
        a = jnp.moveaxis(a, 2, 1)
        return a.reshape(a.shape[:2] + (n_chunks, chunk) + a.shape[3:])

    dA = heads(dt.astype(F32) * A.astype(F32))                 # (B,H,nC,Q)
    cum = jnp.cumsum(dA, axis=-1)[:, :, :, None, :]            # (B,H,nC,1,Q)
    dt_r = heads(dt)[:, :, :, None, :]
    xh, Bh, Ch = heads(x), heads(Bmat), heads(Cmat)

    def head_map(b, g, c, h):
        return (b, g * hpg + h, c, 0, 0)

    def group_map(b, g, c, h):
        return (b, g, c, 0, 0)

    kernel = functools.partial(_ssd_kernel, chunk=chunk, n_chunks=n_chunks)
    y, s_final = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, chunk, P), head_map),
            pl.BlockSpec((1, 1, 1, 1, chunk), head_map),
            pl.BlockSpec((1, 1, 1, 1, chunk), head_map),
            pl.BlockSpec((1, 1, 1, chunk, N), group_map),
            pl.BlockSpec((1, 1, 1, chunk, N), group_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, chunk, P), head_map),
            # one group's final states stay resident across its chunks
            pl.BlockSpec((1, hpg, N, P), lambda b, g, c, h: (b, g, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(xh.shape, x.dtype),
            jax.ShapeDtypeStruct((B, H, N, P), F32),
        ],
        scratch_shapes=[pltpu.VMEM((hpg, N, P), F32)],
        interpret=interpret,
    )(xh, dt_r, cum, Bh, Ch)
    y = jnp.moveaxis(y.reshape(B, H, T, P), 1, 2)
    return y, jnp.swapaxes(s_final, 2, 3)
