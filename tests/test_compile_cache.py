"""utils/compile_cache: the persistent compile cache lives in one directory,
``$JAX_COMPILATION_CACHE_DIR`` when set and ``<checkout>/.jax_cache``
otherwise, and importing the module configures nothing."""
import os

from conftest import REPO, run_with_devices

_ENABLE = """
import os, jax, jax.numpy as jnp
import repro.utils.compile_cache as cc
assert jax.config.jax_compilation_cache_dir != cc.DEFAULT_DIR, "set at import"
path = cc.enable_compile_cache()
assert jax.config.jax_compilation_cache_dir == path
if {compile}:
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()
print(path)
"""


def test_compile_cache_uses_env_dir(tmp_path):
    cache = tmp_path / "cache"
    out = run_with_devices(_ENABLE.format(compile=True), n_devices=1,
                           env_extra={"JAX_COMPILATION_CACHE_DIR": str(cache)})
    assert out.split()[-1] == str(cache)
    assert os.listdir(cache), "no compiled entry landed in the env dir"


def test_compile_cache_defaults_to_checkout():
    out = run_with_devices(_ENABLE.format(compile=False), n_devices=1,
                           env_extra={"JAX_COMPILATION_CACHE_DIR": ""})
    assert out.split()[-1] == os.path.join(REPO, ".jax_cache")
