"""Shared fixtures.  NOTE: no XLA_FLAGS here — smoke tests must see 1 device
(the dry-run alone forces 512).  Multi-device tests spawn subprocesses."""
import os
import subprocess
import sys

import pytest


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(code: str, n_devices: int = 8, timeout: int = 300,
                     env_extra: dict = None):
    """Run python code in a subprocess with forced host devices."""
    env = dict(os.environ, **(env_extra or {}))
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["JAX_PLATFORMS"] = "cpu"  # forced host devices; the chip stays free
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=timeout)
    if r.returncode != 0:
        raise AssertionError(f"subprocess failed:\n{r.stdout}\n{r.stderr}")
    return r.stdout


@pytest.fixture(scope="session")
def rng():
    import jax
    return jax.random.PRNGKey(0)
