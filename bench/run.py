#!/usr/bin/env python3
"""Run one benchmark cell and print its result as one JSON line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic are found by name through
``BENCHMARK.json``: ``bench/traffic/<traffic>.json`` names the job
(``bench/jobs/<job>.py``) that loads, warms up, measures for ``--seconds``
and checks what the timed path produced against a plain reference.  Each
metric of the cell is read by ``bench/metrics/<metric>.py``: with
``--trace 0`` the end-to-end metrics, with ``--trace 1`` the per-layer ones,
read from the run's record and a profiler trace of the window.

There is no CPU fallback: without a TPU, or with fewer chips than the cell
asks for, the run exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import common  # noqa: E402


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def check_devices(chips: int):
    """The devices to run on; exits 2 unless JAX sees ``chips`` TPUs."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX's first device is {devices[0].platform!r}",
              file=sys.stderr)
        sys.exit(2)
    if len(devices) < chips:
        print(f"the cell asks for {chips} chips, JAX sees {len(devices)}",
              file=sys.stderr)
        sys.exit(2)
    return devices


def read_metrics(defs, run, trace_summary, bench: str) -> dict:
    """Each metric's reader on this run; a reader that finds nothing to
    read returns None and its metric is left out."""
    out = {}
    for m in defs:
        value = common.load_module("metrics", m["name"], bench).read(
            run, trace_summary)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(spec: dict, args, *, out_dir: str, devices) -> dict:
    """Drive the cell's job through one run; returns the result object."""
    traffic = spec["traffic"]
    run = common.Run(t_process=T_PROCESS, seed=args.seed,
                     seconds=args.seconds, trace=bool(args.trace),
                     trace_seconds=float(traffic.get("trace_seconds", 3.0)),
                     out_dir=out_dir)
    run.device_kind, run.n_chips = devices[0].device_kind, len(devices)
    job = common.load_module("jobs", traffic["job"], spec["bench"])
    job.run(run, spec)

    trace_summary = None
    if run.trace:
        from bench import trace_reduce

        t0, t1 = run.traced_window
        trace_summary = trace_reduce.reduce(run.profile_dir,
                                            window_s=t1 - t0)
    defs = spec["per_layer"] if run.trace else spec["end_to_end"]
    metrics = read_metrics(defs, run, trace_summary, spec["bench"])
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    result = {"correct": bool(run.checks) and all(c.ok for c in run.checks),
              "attempted": int(run.record.get("attempted", 0)),
              "failed": int(run.record.get("failed", 0)),
              "metrics": metrics, "device": device}
    if trace_summary is not None:
        device["busy_s"] = trace_summary.busy_s
        device["window_s"] = trace_summary.window_s
        result["breakdown"] = trace_summary.breakdown()
    result["window"] = {k: run.record[k] for k in
                        ("recompiles", "call_s_median", "call_s_max")
                        if k in run.record}
    result["checks"] = {c.name: c.as_json() for c in run.checks}
    return result


def report(result: dict) -> None:
    """The numbers compared, beside their limits, as the last lines of
    stderr; the result as the last line of stdout."""
    sys.stdout.flush()
    print(f"attempted {result['attempted']} failed {result['failed']} "
          f"window {result['window']} "
          f"memory_peak_bytes {result['device']['memory_peak_bytes']}",
          file=sys.stderr)
    for name, c in result["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} {verdict}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def main(argv=None, *, require_tpu: bool = True) -> int:
    args = parse_args(argv)
    spec = common.cell_spec(common.benchmark(), args.workload)
    common.add_program_to_path()
    common.enable_cache()
    import jax

    if require_tpu:
        devices = check_devices(spec["cell"]["chips"])
    else:
        devices = jax.devices()
    with tempfile.TemporaryDirectory(prefix="bench-") as out_dir:
        result = run_cell(spec, args, out_dir=out_dir, devices=devices)
    report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
