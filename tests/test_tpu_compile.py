"""The Pallas kernels compile for a TPU v5e at the widths the main path runs.

Nothing runs here: each test lowers one kernel op with ``interpret=False``
for a described (not attached) v5e chip and asserts the compiled program
holds the Mosaic kernel (``tpu_custom_call``).  This catches what interpret
mode cannot — block shapes the TPU tiling refuses, primitives Mosaic does
not lower, kernels that overflow VMEM.

Widths (shared with ``chip_smoke.py``):
  attention: glm4-9b heads (H=32, Hkv=2, dh=128) at T=2048; mixtral-8x7b
             heads (Hkv=8) with window=4096; decode against S=4096, B=8
  SSD:       mamba2-1.3b (H=64, P=64, N=128, G=1), chunk 256, T=2048
  sum tree:  capacity 2^20, batch 256
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.ops import (flash_attention,
                                               flash_attention_decode)
from repro.kernels.ssd_scan.ops import ssd_scan
from repro.kernels.sum_tree.ops import tree_sample_blocked

BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip cannot read a persistent-cache entry back
    jax.config.update("jax_enable_compilation_cache", False)
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _attn(T, H, Hkv, window=None):
    def fn(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               interpret=False)
    return fn, [((1, T, H, 128), BF16), ((1, T, Hkv, 128), BF16),
                ((1, T, Hkv, 128), BF16)]


def _decode(H, Hkv, S=4096, B=8):
    def fn(q, k, v, kv_len):
        return flash_attention_decode(q, k, v, kv_len, interpret=False)
    return fn, [((B, 1, H, 128), BF16), ((B, S, Hkv, 128), BF16),
                ((B, S, Hkv, 128), BF16), ((B,), jnp.int32)]


def _ssd(T=2048, H=64, P=64, N=128):
    def fn(x, dt, A, Bm, Cm):
        return ssd_scan(x, dt, A, Bm, Cm, chunk=256, interpret=False)
    return fn, [((1, T, H, P), F32), ((1, T, H), F32), ((H,), F32),
                ((1, T, 1, N), F32), ((1, T, 1, N), F32)]


def _sum_tree(capacity=2 ** 20, batch=256):
    def fn(tree, u):
        return tree_sample_blocked(tree, u, interpret=False)
    return fn, [((2 * capacity,), F32), ((batch,), F32)]


CASES = {
    "attention_train_glm4": lambda: _attn(2048, 32, 2),
    "attention_train_mixtral_window": lambda: _attn(2048, 32, 8, window=4096),
    "attention_decode_glm4": lambda: _decode(32, 2),
    "attention_decode_mixtral": lambda: _decode(32, 8),
    "ssd_mamba2": _ssd,
    "sum_tree_sample": _sum_tree,
    # DeviceReplay at the Catch DQN example's size (examples/, chip_smoke.py)
    "sum_tree_sample_catch_replay": lambda: _sum_tree(2 ** 13, 64),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip):
    fn, shapes = CASES[case]()
    compiled = _compile(fn, shapes, one_chip)
    assert "tpu_custom_call" in compiled.as_text()
