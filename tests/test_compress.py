"""int8 error-feedback gradient compression (train/compress.py).

Single-device math first — the quantizer's roundtrip bound, the residual
telescoping identity, the zero/non-finite edge cases that feed the nan_guard
sentinel — then the collective itself on a forced multi-device mesh:
cross_pod_allreduce must track lax.pmean to within the per-step quantization
bound, and the wire-bytes accounting must match the 4x payload story the
roofline uses.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from conftest import run_with_devices

from repro.train.compress import (EFState, cross_pod_allreduce, ef_dequantize,
                                  ef_quantize, init_ef, wire_bytes)


def test_roundtrip_bound():
    """|(x + r) - q*scale| <= scale elementwise, across magnitudes."""
    rng = np.random.RandomState(0)
    for mag in (1e-6, 1.0, 1e4):
        x = jnp.asarray(rng.randn(64, 33) * mag, jnp.float32)
        r = jnp.asarray(rng.randn(64, 33) * mag * 0.1, jnp.float32)
        q, scale, new_r = ef_quantize(x, r)
        assert q.dtype == jnp.int8
        err = np.abs(np.asarray(x + r) - np.asarray(ef_dequantize(q, scale)))
        assert err.max() <= float(scale) * (1 + 1e-6)
        # the residual IS that error (what EF carries to the next step)
        np.testing.assert_allclose(np.asarray(new_r),
                                   np.asarray(x + r) - np.asarray(
                                       ef_dequantize(q, scale)), rtol=1e-6)


def test_residual_telescoping_identity():
    """Over T steps the dequantized stream sums to the true stream minus the
    final residual: sum_t deq_t = sum_t x_t - r_T (exact, the EF guarantee)."""
    rng = np.random.RandomState(1)
    xs = [jnp.asarray(rng.randn(17, 5), jnp.float32) for _ in range(8)]
    r = jnp.zeros((17, 5), jnp.float32)
    deq_sum = jnp.zeros_like(r)
    for x in xs:
        q, scale, r = ef_quantize(x, r)
        deq_sum = deq_sum + ef_dequantize(q, scale)
    np.testing.assert_allclose(np.asarray(deq_sum + r),
                               np.asarray(sum(xs)), rtol=1e-4, atol=1e-5)


def test_zero_input_stays_zero():
    q, scale, r = ef_quantize(jnp.zeros((8, 8)), jnp.zeros((8, 8)))
    assert np.all(np.asarray(q) == 0)
    assert np.isfinite(float(scale))
    np.testing.assert_array_equal(np.asarray(r), 0.0)


@pytest.mark.parametrize("bad", [jnp.inf, -jnp.inf, jnp.nan])
def test_nonfinite_input_poisons_scale_and_fires_nan_guard(bad):
    """int8 cast of inf/nan is finite garbage — the quantizer must poison the
    scale so deq + residual go nan and count_nonfinite (the nan_guard
    sentinel's channel) sees them."""
    from repro.telemetry.sentinels import count_nonfinite
    x = jnp.ones((4, 4)).at[1, 2].set(bad)
    q, scale, r = ef_quantize(x, jnp.zeros((4, 4)))
    assert not np.isfinite(float(scale))
    deq = ef_dequantize(q, scale)
    assert int(count_nonfinite(deq)) > 0
    assert int(count_nonfinite(r)) > 0


def test_wire_bytes_accounting():
    tree = {"a": jnp.zeros((10, 10)), "b": jnp.zeros((5,))}
    wb = wire_bytes(tree)
    assert wb["fp32_bytes"] == 4 * 105
    assert wb["int8_bytes"] == 105 + 4 * 2  # payload + one fp32 scale/tensor
    assert wb["bytes_saved"] == wb["fp32_bytes"] - wb["int8_bytes"]
    assert 3.5 < wb["ratio"] < 4.0


def test_cross_pod_allreduce_matches_pmean_within_bound():
    """On a forced 4-device mesh, the compressed all-reduce equals lax.pmean
    up to the mean of the per-shard quantization bounds (amax/127), and a
    second step on the SAME grads tightens toward exactness (error feedback
    re-sends what quantization dropped)."""
    run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.train.compress import EFState, cross_pod_allreduce

from repro.launch.mesh import auto_mesh
mesh = auto_mesh((4,), ("pod",))
gs = jax.random.normal(jax.random.PRNGKey(0), (4, 32, 8), jnp.float32)

def step(g, r):
    out, ef = cross_pod_allreduce({"w": g[0]}, EFState(residual={"w": r[0]}),
                                  axis="pod")
    ref = jax.lax.pmean(g[0], "pod")
    return out["w"][None], ef.residual["w"][None], ref[None]

f = jax.jit(jax.shard_map(step, mesh=mesh, in_specs=(P("pod"), P("pod")),
                          out_specs=(P("pod"), P("pod"), P("pod")),
                          check_vma=False))
r = jnp.zeros_like(gs)
out1, r, ref = f(gs, r)
bound = float(np.mean(np.abs(np.asarray(gs)).max(axis=(1, 2)) / 127.0))
err1 = float(np.abs(np.asarray(out1[0]) - np.asarray(ref[0])).max())
assert err1 <= bound * (1 + 1e-5), (err1, bound)
assert err1 > 0  # quantization IS lossy on random floats
# all shards agree on the reduced value
np.testing.assert_allclose(np.asarray(out1[0]), np.asarray(out1[-1]))
# EF: re-reducing the same grads applies the dropped part; the SUM of the
# two applied updates lands within one quantization bound of 2x the truth
out2, r, _ = f(gs, r)
err2 = float(np.abs(np.asarray(out1[0] + out2[0]) -
                    2 * np.asarray(ref[0])).max())
assert err2 <= bound * (1 + 1e-5), (err2, bound)
print("allreduce-vs-pmean ok")
""", n_devices=4)


def test_init_ef_structure():
    tree = {"a": jnp.zeros((3, 2), jnp.bfloat16), "b": jnp.zeros((4,))}
    ef = init_ef(tree)
    assert ef.residual["a"].dtype == jnp.float32
    assert ef.residual["a"].shape == (3, 2)
    assert ef.residual["b"].shape == (4,)
