"""Prioritized-replay stratified sampling Pallas TPU kernel.

rlpyt's replay hot spot is the sum-tree descent — a pointer-chasing binary
search that is hostile to TPUs.  TPU-native re-think: store priorities as
(n_blocks, block_size) leaves plus per-block sums; sampling is then (1) a
prefix sum and compare over block sums to pick the block and (2) a row
select + prefix sum and compare within the block — all dense vector and
MXU ops, no tree pointers.  O(n/bs + bs) work per sample instead of
O(log n) serial hops.

The TPU lowering has no in-kernel ``cumsum`` or gather, so prefix sums are
matmuls against triangular ones matrices and the row select is a one-hot
matmul, all at ``Precision.HIGHEST`` (fp32 contraction): a one-hot row
picks its f32 value exactly.  Block sums arrive as ``(n_blocks/128, 128)``
lane rows (zero-padded), u and the outputs as ``(batch, 1)`` columns.

Grid: (batch / block_b,) — each grid step resolves block_b samples with the
whole priority table resident in VMEM (2^20 f32 = 4 MiB at bs=512).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

F32 = jnp.float32
LANES = 128
HIGHEST = jax.lax.Precision.HIGHEST


def _upper_ones(n):
    """(n, n) with ones where row <= col: ``x @ U`` is x's inclusive prefix
    sum along its last axis."""
    r = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return (r <= c).astype(F32)


def _sample_kernel(leaves_ref, bsums_ref, u_ref, idx_ref, prob_ref, *,
                   n_blocks, block_size):
    u = u_ref[...]                                    # (bb, 1)
    bb = u.shape[0]
    # global inclusive prefix over block sums, one 128-lane row at a time
    rowpre = jnp.dot(bsums_ref[...], _upper_ones(LANES),
                     preferred_element_type=F32, precision=HIGHEST)
    lane = jax.lax.broadcasted_iota(jnp.int32, (bb, LANES), 1)
    running = jnp.zeros((1, 1), F32)
    cums = []
    for r in range(rowpre.shape[0]):
        cums.append(rowpre[r:r + 1, :] + running)    # (1, 128)
        running = running + rowpre[r:r + 1, LANES - 1:LANES]
    total = running

    # block = #blocks whose inclusive prefix is <= u (smallest cum > u)
    blk = sum(jnp.sum((c <= u).astype(jnp.int32), axis=1, keepdims=True)
              for c in cums)
    blk = jnp.minimum(blk, n_blocks - 1)               # (bb, 1)
    base = sum(jnp.sum(jnp.where(lane + r * LANES == blk - 1, c, 0.0),
                       axis=1, keepdims=True)
               for r, c in enumerate(cums))
    off = u - base                                    # mass left in block

    onehot = (jax.lax.broadcasted_iota(jnp.int32, (bb, n_blocks), 1)
              == blk).astype(F32)
    rows = jnp.dot(onehot, leaves_ref[...], preferred_element_type=F32,
                   precision=HIGHEST)                 # (bb, bs)
    cum2 = jnp.dot(rows, _upper_ones(block_size), preferred_element_type=F32,
                   precision=HIGHEST)
    inner = jnp.sum((cum2 <= off).astype(jnp.int32), axis=1, keepdims=True)
    inner = jnp.minimum(inner, block_size - 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (bb, block_size), 1)
    pr = jnp.sum(jnp.where(col == inner, rows, 0.0), axis=1, keepdims=True)

    idx_ref[...] = blk * block_size + inner
    prob_ref[...] = pr / jnp.maximum(total, 1e-12)


def sample_pallas(leaves, block_sums, u, *, block_b: int = 256,
                  interpret: bool = True):
    """leaves: (n_blocks, bs) f32; block_sums: (n_blocks,) f32;
    u: (batch,) f32 in [0, total).  Returns (idx (batch,) i32, prob (batch,))."""
    n_blocks, bs = leaves.shape
    batch = u.shape[0]
    block_b = min(block_b, batch)
    assert batch % block_b == 0
    grid = (batch // block_b,)
    pad = (-n_blocks) % LANES
    bsums = jnp.pad(block_sums.astype(F32), (0, pad)).reshape(-1, LANES)

    kernel = functools.partial(_sample_kernel, n_blocks=n_blocks,
                               block_size=bs)
    idx, prob = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((n_blocks, bs), lambda i: (0, 0)),
            pl.BlockSpec(bsums.shape, lambda i: (0, 0)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch, 1), jnp.int32),
            jax.ShapeDtypeStruct((batch, 1), F32),
        ],
        interpret=interpret,
    )(leaves.astype(F32), bsums, u.astype(F32).reshape(batch, 1))
    return idx[:, 0], prob[:, 0]
