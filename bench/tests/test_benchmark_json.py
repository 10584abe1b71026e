"""BENCHMARK.json keeps to the benchmark's contract, and every name in it
finds its files."""
import json
import os
import re

import pytest

from bench import common

BM = common.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TEXT = re.compile(r"^[^\t\n]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def all_names():
    out = []
    for c in BM["configs"]:
        out += [c["name"]] + c["reduced"]
    for w in BM["workloads"]:
        out += [w["name"], w["config"], w["traffic"]]
    out += [m["name"] for m in BM["end_to_end"] + BM["per_layer"]]
    return out


def test_top_level_keys():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["command"] == ["python3", "bench/run.py"]
    assert BM["paths"] == ["bench"]
    assert 1 <= BM["run_seconds"] <= 51
    assert len(json.dumps(BM)) < 64 * 1024


@pytest.mark.parametrize("name", all_names())
def test_names_use_allowed_characters(name):
    assert NAME.match(name), name


def test_units_texts_and_sources():
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in BM["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for c in BM["configs"]:
        assert TEXT.match(c["why"]) and TEXT.match(c["source"])
    for w in BM["workloads"]:
        assert TEXT.match(w["why"]) and w["chips"] in (1, 4)
    for m in BM["per_layer"]:
        assert TEXT.match(m["layer"])


def test_names_are_unique():
    for group in ("configs", "workloads"):
        names = [x["name"] for x in BM[group]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in BM["end_to_end"] + BM["per_layer"]]
    assert len(metrics) == len(set(metrics))
    pairs = [(w["config"], w["traffic"]) for w in BM["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("cell", [w["name"] for w in BM["workloads"]])
def test_cell_names_existing_files(cell):
    spec = common.cell_spec(BM, cell)
    bench = spec["bench"]
    job = spec["traffic"]["job"]
    assert os.path.isfile(os.path.join(bench, "jobs", job + ".py"))
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert os.path.isfile(os.path.join(bench, "metrics",
                                           m["name"] + ".py")), m["name"]
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert spec["per_layer"]
    for m in spec["per_layer"]:
        assert m["moves"] in e2e


def test_every_config_is_used_and_its_file_is_its_own():
    used = {w["config"] for w in BM["workloads"]}
    files = [c["file"] for c in BM["configs"]]
    assert len(files) == len(set(files))
    for c in BM["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("bench/")
        body = common.load_json(os.path.join(common.ROOT, c["file"]))
        assert body["reduced"] == c["reduced"]
        assert body["source"] == c["source"]
