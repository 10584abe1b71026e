"""The plain reference against a naive loop, and its float8 control against
the exact form."""
import jax
import jax.numpy as jnp
import numpy as np

from bench import weights
from bench.reference import lm_ppo, mamba2
from bench.tests.smoke import SMOKE_MODEL


def _naive_ssd(x, dt, A, Bm, Cm):
    """The recurrence h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,
    y_t = h_t C_t, one step at a time in float64."""
    x, dt, A, Bm, Cm = (np.asarray(a, np.float64) for a in (x, dt, A, Bm, Cm))
    Bsz, T, H, P = x.shape
    rep = H // Bm.shape[2]
    y = np.zeros_like(x)
    for b in range(Bsz):
        for h in range(H):
            g = h // rep
            state = np.zeros((P, Bm.shape[3]))
            for t in range(T):
                state = np.exp(dt[b, t, h] * A[h]) * state + \
                    dt[b, t, h] * np.outer(x[b, t, h], Bm[b, t, g])
                y[b, t, h] = state @ Cm[b, t, g]
    return y


def test_ssd_quadratic_matches_the_recurrence():
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    B, T, H, P, G, N = 2, 12, 4, 3, 2, 5
    x = jax.random.normal(ks[0], (B, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, T, G, N))
    Cm = jax.random.normal(ks[4], (B, T, G, N))
    got = mamba2.ssd_quadratic(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(got), _naive_ssd(x, dt, A, Bm, Cm),
                               rtol=1e-4, atol=1e-4)


def test_causal_conv_matches_a_loop():
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 7, 3))
    w = jax.random.normal(jax.random.PRNGKey(2), (4, 3))
    got = np.asarray(mamba2.causal_conv_silu(x, w))
    xn, wn = np.asarray(x, np.float64), np.asarray(w, np.float64)
    want = np.zeros_like(xn)
    for t in range(7):
        for i in range(4):
            s = t - 3 + i
            if s >= 0:
                want[:, t] += xn[:, s] * wn[i]
    want = want / (1 + np.exp(-want))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_fp8_control_departs_from_the_exact_forward():
    params = weights.make_mamba2(3, SMOKE_MODEL, 256)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 16), 0, 256)
    exact, _ = mamba2.logits_values(params, tokens)
    low, _ = mamba2.logits_values(params, tokens, quant=True)
    gap = float(jnp.max(jnp.abs(exact - low)))
    scale = float(jnp.max(jnp.abs(exact)))
    assert 1e-3 * scale < gap < scale


def test_gae_matches_the_closed_form():
    r = jnp.array([[1.0], [2.0], [3.0]])
    v = jnp.array([[0.5], [0.25], [0.125]])
    done = jnp.array([[0.0], [0.0], [1.0]])
    adv, ret = lm_ppo.gae(r, v, done, gamma=0.5, lam=0.5)
    d2 = 3.0 - 0.125
    d1 = 2.0 + 0.5 * 0.125 - 0.25
    d0 = 1.0 + 0.5 * 0.25 - 0.5
    want = [d0 + 0.25 * (d1 + 0.25 * d2), d1 + 0.25 * d2, d2]
    np.testing.assert_allclose(np.asarray(adv[:, 0]), want, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(ret - adv), np.asarray(v))
