"""Run a cell's job at a size the CPU holds, through the harness's own
``run_cell``, skipping only its look for a chip."""
from __future__ import annotations

import argparse
import functools
import tempfile

from bench import common

SMOKE_MODEL = {"name": "mamba2-smoke", "family": "ssm", "n_layers": 2,
               "d_model": 64, "n_heads": 0, "n_kv_heads": 0, "d_head": 0,
               "d_ff": 0, "vocab": 256, "d_state": 16, "ssm_headdim": 16,
               "ssm_expand": 2, "ssm_n_groups": 1, "conv_kernel": 4,
               "ssd_chunk": 8, "remat": False}

SMOKE_TRAFFIC = {
    "lm_ppo": {"batch": 4, "horizon": 8, "trace_seconds": 0.5},
    "serve": {"n_slots": 4, "arrivals": {"process": "poisson", "rate": 4.0},
              "prompt": {"dist": "lognormal", "median": 12, "sigma": 0.4,
                         "min": 8, "max": 16},
              "output": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                         "min": 4, "max": 16},
              "check_requests": 4, "trace_seconds": 0.5},
}


def smoke_spec(workload: str, root: str = common.ROOT, **traffic) -> dict:
    spec = common.cell_spec(common.benchmark(root), workload, root)
    spec["config"] = dict(spec["config"], model=SMOKE_MODEL, padded_vocab=256)
    job = spec["traffic"]["job"]
    spec["traffic"] = dict(spec["traffic"], **SMOKE_TRAFFIC[job], **traffic)
    return spec


def run_smoke(spec: dict, *, seed: int = 2 ** 31 + 17, seconds: float = 1.5,
              trace: int = 0, fault: str = None) -> dict:
    """The result object of one run at smoke size on the CPU."""
    import jax

    from bench import run as harness

    args = argparse.Namespace(workload=spec["cell"]["name"], seed=seed,
                              seconds=seconds, trace=trace)
    original = common.load_module

    def load(kind, name, bench=common.BENCH):
        mod = original(kind, name, bench)
        if kind == "jobs" and fault is not None:
            mod.run = functools.partial(mod.run, fault=fault)
        return mod

    with tempfile.TemporaryDirectory() as out_dir:
        common.load_module = load
        try:
            return harness.run_cell(spec, args, out_dir=out_dir,
                                    devices=jax.devices())
        finally:
            common.load_module = original
