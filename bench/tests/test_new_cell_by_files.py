"""A configuration, a traffic mix and a per-layer metric are added by new
files under bench/ and new entries in BENCHMARK.json alone: a cell that
exists only in this test's fixture runs through the unchanged harness."""
import json
import os
import shutil

import pytest

from bench import common
from bench.tests.smoke import SMOKE_MODEL, run_smoke


@pytest.fixture
def root(tmp_path):
    shutil.copytree(common.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bm = common.benchmark()
    bm["configs"].append({"name": "mamba2-tiny", "source": "test fixture",
                          "file": "bench/configs/mamba2-tiny.json",
                          "reduced": [], "why": "a fixture"})
    bm["workloads"].append({"name": "serve-tiny", "config": "mamba2-tiny",
                            "traffic": "serve-tiny", "chips": 1,
                            "why": "a fixture"})
    bm["per_layer"].append({"name": "served_tokens", "unit": "tokens",
                            "better": "higher", "source": "program_counter",
                            "layer": "serving engine",
                            "moves": "serve_tokens_per_s",
                            "workloads": ["serve-tiny"]})
    for m in bm["end_to_end"] + bm["per_layer"]:
        if "serve-mamba2-chat" in m.get("workloads", []):
            m["workloads"].append("serve-tiny")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    cfg = {"model": SMOKE_MODEL, "padded_vocab": 256, "source": "test fixture",
           "reduced": []}
    (tmp_path / "bench/configs/mamba2-tiny.json").write_text(json.dumps(cfg))
    mix = json.loads((tmp_path / "bench/traffic/serve-chat.json").read_text())
    mix.update(n_slots=2, check_requests=2, trace_seconds=0.5,
               arrivals={"process": "poisson", "rate": 3.0},
               prompt={"dist": "fixed", "value": 8, "min": 8, "max": 8},
               output={"dist": "fixed", "value": 6, "min": 6, "max": 6})
    (tmp_path / "bench/traffic/serve-tiny.json").write_text(json.dumps(mix))
    (tmp_path / "bench/metrics/served_tokens.py").write_text(
        "def read(run, trace):\n"
        "    return run.record['generated_tokens']\n")
    return str(tmp_path)


def test_fixture_cell_runs_through_the_harness(root):
    spec = common.cell_spec(common.benchmark(root), "serve-tiny", root)
    assert spec["bench"] == os.path.join(root, "bench")
    res = run_smoke(spec, trace=1)
    assert res["metrics"]["served_tokens"]["value"] == 6 * res["attempted"]
