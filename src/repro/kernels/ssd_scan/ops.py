"""Public SSD-scan op behind the kernel backend registry.

Forward is the Pallas chunked-scan kernel (interpret or compiled per the
registry); backward is a ``custom_vjp`` through the pure-jnp chunked scan
(``models.layers.ssd_chunked``) so the fused mamba2/zamba2 train step
differentiates through the op unchanged.  Pads T to a chunk multiple
(dt=0 padding adds no state contribution — same convention as the ref).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .. import registry
from .ssd_scan import ssd_scan_pallas


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _ssd_impl(x, dt, A, Bmat, Cmat, *, chunk, interpret):
    B, T, H, P = x.shape
    pad = (-T) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bmat = jnp.pad(Bmat, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cmat = jnp.pad(Cmat, ((0, 0), (0, pad), (0, 0), (0, 0)))
    y, s = ssd_scan_pallas(x, dt, A, Bmat, Cmat, chunk=chunk,
                           interpret=interpret)
    return y[:, :T], s


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _ssd(x, dt, A, Bmat, Cmat, opts):
    chunk, interpret = opts
    return _ssd_impl(x, dt, A, Bmat, Cmat, chunk=chunk, interpret=interpret)


def _ssd_fwd(x, dt, A, Bmat, Cmat, opts):
    return _ssd(x, dt, A, Bmat, Cmat, opts), (x, dt, A, Bmat, Cmat)


def _ssd_bwd(opts, res, g):
    # Backward recomputes through the jnp chunked scan and lets XLA
    # differentiate it.  Lazy import: ref -> models.layers -> (flash
    # attention ops) would cycle at module-import time otherwise.
    from ...models.layers import ssd_chunked

    chunk = opts[0]
    x, dt, A, Bmat, Cmat = res
    _, vjp = jax.vjp(
        lambda x_, dt_, A_, B_, C_: ssd_chunked(x_, dt_, A_, B_, C_, chunk),
        x, dt, A, Bmat, Cmat)
    return vjp(g)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd_scan(x, dt, A, Bmat, Cmat, *, chunk: int = 64,
             interpret: Optional[bool] = None):
    """x:(B,T,H,P) dt:(B,T,H) A:(H,)<0  B/C:(B,T,G,N) -> (y, final_state).
    Differentiable (custom_vjp; backward via the jnp chunked scan)."""
    interpret = registry.resolve_interpret("ssd", interpret)
    return _ssd(x, dt, A, Bmat, Cmat, (chunk, interpret))
