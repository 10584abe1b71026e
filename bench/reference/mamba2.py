"""Plain float32 reference of the mamba2 LM the program runs.

Straightforward ``jax.numpy`` at "highest" matmul precision, no kernels,
caches or batching tricks.  The state-space layer is computed in its
quadratic (attention-like) form over the whole sequence,
``y_t = sum_{s<=t} exp(c_t - c_s) (C_t . B_s) dt_s x_s`` with
``c_t = sum_{r<=t} dt_r A``, which is the SSD duality's exact masked form
and shares no code path with the program's chunked scan, its Pallas kernel
or its one-token recurrence.

It follows Mamba-2 (arXiv:2405.21060) as the program defines it; the
departures from the published mamba2-1.3b are the program's and are listed
in the configuration file: no D skip, no convolution bias, an untied LM
head, RMSNorm epsilon 1e-6, and a value head for PPO.

``quant`` computes in float8 (e4m3) what the program computes in
bfloat16: every weight product from float8 operands, and every activation
the program keeps in bfloat16 (the residual stream, the projections, the
convolution, the gate) rounded to float8.  That is the control that
``correct`` has to fail.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
EPS = 1e-6


def exact_matmul(eq, a, b):
    return jnp.einsum(eq, a.astype(F32), b.astype(F32), precision=HIGHEST)


def _fp8(x):
    """Per-tensor scaled float8_e4m3fn rounding of ``x`` in the forward
    pass; the gradient passes straight through in float32, as in fp8
    training, so the control's gradients are not flushed to zero."""
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    q = (x * scale).astype(jnp.float8_e4m3fn).astype(F32) / scale
    return x + jax.lax.stop_gradient(q - x)


def fp8_matmul(eq, a, b):
    """A weight product computed from float8 (e4m3) operands."""
    return exact_matmul(eq, _fp8(a.astype(F32)), _fp8(b.astype(F32)))


def rmsnorm(x, scale):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + EPS) * scale


def ssd_quadratic(x, dt, A, Bm, Cm):
    """x (B,T,H,P), dt (B,T,H), A (H,), Bm/Cm (B,T,G,N) -> y (B,T,H,P)."""
    Bsz, T, H, P = x.shape
    G = Bm.shape[2]
    rep = H // G
    Bh = jnp.repeat(Bm, rep, axis=2)
    Ch = jnp.repeat(Cm, rep, axis=2)
    c = jnp.cumsum(dt * A, axis=1)                            # (B,T,H)
    tri = jnp.tril(jnp.ones((T, T), bool))                    # [t, s]
    diff = c[:, :, None, :] - c[:, None, :, :]                # (B,t,s,H)
    decay = jnp.where(tri[None, :, :, None],
                      jnp.exp(jnp.where(tri[None, :, :, None], diff, 0.0)),
                      0.0)
    cb = jnp.einsum("bthn,bshn->btsh", Ch, Bh, precision=HIGHEST)
    w = cb * decay * dt[:, None, :, :]
    return jnp.einsum("btsh,bshp->bthp", w, x, precision=HIGHEST)


def causal_conv_silu(x, w):
    """Depthwise causal convolution over time, then SiLU.  x (B,T,C),
    w (K,C)."""
    K, T = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    y = sum(xp[:, i:i + T] * w[i] for i in range(K))
    return jax.nn.silu(y)


def _exact(x):
    return x


def mixer(p, h, mm, act=_exact):
    """One mamba2 mixer on normalised input h (B,T,D).  ``act`` rounds the
    activations that the program keeps in its compute type."""
    Bsz, T, D = h.shape
    _, H, P = p["wz"].shape
    _, G, N = p["wB"].shape
    z = act(mm("btd,dhp->bthp", h, p["wz"]))
    xs = act(mm("btd,dhp->bthp", h, p["wx"]))
    Bm = act(mm("btd,dgn->btgn", h, p["wB"]))
    Cm = act(mm("btd,dgn->btgn", h, p["wC"]))
    dtr = act(mm("btd,dh->bth", h, p["wdt"]))
    xbc = jnp.concatenate([xs.reshape(Bsz, T, H * P),
                           Bm.reshape(Bsz, T, G * N),
                           Cm.reshape(Bsz, T, G * N)], axis=-1)
    xbc = act(causal_conv_silu(xbc, p["conv_w"]))
    xs = xbc[..., :H * P].reshape(Bsz, T, H, P)
    Bm = xbc[..., H * P:H * P + G * N].reshape(Bsz, T, G, N)
    Cm = xbc[..., H * P + G * N:].reshape(Bsz, T, G, N)
    dt = jax.nn.softplus(dtr + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    y = act(ssd_quadratic(xs, dt, A, Bm, Cm)).reshape(Bsz, T, H * P)
    y = act(rmsnorm(act(y * jax.nn.silu(z.reshape(Bsz, T, H * P))),
                    p["norm_scale"]))
    return act(mm("bthp,hpd->btd", y.reshape(Bsz, T, H, P), p["out_proj"]))


def hidden_states(params, tokens, *, quant=False, remat=False):
    """Final normalised hidden states (B,T,D) for tokens (B,T)."""
    mm, act = (fp8_matmul, _fp8) if quant else (exact_matmul, _exact)
    x = act(params["tok_embed"].astype(F32)[tokens])

    def layer(x, lp):
        h = act(rmsnorm(x, lp["norm"]["scale"]))
        return act(x + mixer(lp["ssd"], h, mm, act)), None

    fn = jax.checkpoint(layer) if remat else layer
    x, _ = jax.lax.scan(fn, x, params["blocks"])
    return act(rmsnorm(x, params["final_norm"]["scale"])), mm


def logits_values(params, tokens, *, quant=False, remat=False):
    """(logits (B,T,V_padded), values (B,T)) in float32."""
    h, mm = hidden_states(params, tokens, quant=quant, remat=remat)
    logits = mm("btd,dv->btv", h, params["lm_head"])
    values = mm("btd,dk->btk", h, params["value_head"])[..., 0]
    return logits, values


def served_token_gaps(params, seqs, first, count, *, control=False):
    """How far each served token's logit lies below the reference's best.

    ``seqs`` (n, T) int32 holds each request's prompt followed by the
    tokens served for it (padded at the end); request i's served tokens
    sit at positions first[i] .. first[i] + count[i] - 1.  Returns the
    (n, T - 1) gaps, zero where nothing was served.  With ``control`` the
    token judged at each position is the one the float8 forward ranks
    first, not the served one."""
    ref, _ = logits_values(params, seqs)
    ref = ref[:, :-1]
    if control:
        low, _ = logits_values(params, seqs, quant=True)
        judged = jnp.argmax(low[:, :-1], axis=-1)
    else:
        judged = seqs[:, 1:]
    pos = jnp.arange(seqs.shape[1] - 1)[None, :] + 1
    served = (pos >= first[:, None]) & (pos < (first + count)[:, None])
    gap = jnp.max(ref, axis=-1) - jnp.take_along_axis(
        ref, judged[..., None], axis=-1)[..., 0]
    return jnp.where(served, gap, 0.0)
