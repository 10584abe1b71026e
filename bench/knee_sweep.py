#!/usr/bin/env python3
"""Find the serving knee: the highest offered rate with no growing backlog.

    python3 bench/knee_sweep.py --workload serve-mamba2-chat --rates 6,8,10 \
        --seconds 20

One process, one engine (weights and warm-up once), then for each rate the
cell's mix at that rate for ``--seconds``, drained.  Per rate it prints one
JSON line: requests due, finished, rejected, the TTFT and TPOT p50/p95, and
how long the run took past the window's end (the backlog left to drain).
The cell's rate is fixed in its traffic file from this sweep, once; the
benchmark's runs never search for it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import common, traffic  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", default="serve-mamba2-chat")
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    spec = common.cell_spec(common.benchmark(), args.workload)
    common.add_program_to_path()
    common.enable_cache()
    from bench import weights

    serve = common.load_module("jobs", "serve")
    from repro.serving.workload import Request

    tr, model = spec["traffic"], spec["config"]["model"]
    params = weights.make_mamba2(args.seed, model,
                                 spec["config"]["padded_vocab"])
    engine = serve.build_engine(spec, params, args.seed)
    engine.warmup()
    for rate in [float(r) for r in args.rates.split(",")]:
        mix = dict(tr, arrivals=dict(tr["arrivals"], rate=rate))
        specs = traffic.requests(args.seed, args.seconds, mix, model["vocab"])
        reqs = [Request(rid=i, prompt=s.prompt, max_tokens=s.max_tokens,
                        arrival_s=s.due_s) for i, s in enumerate(specs)]
        t0 = time.perf_counter()
        summary = engine.run(reqs)
        run_s = time.perf_counter() - t0
        ttft, tpot, failed = serve.latencies(reqs, args.seconds,
                                             tr["drain_s"])
        print(json.dumps({
            "rate": rate, "due": len(reqs), "failed": failed,
            "rejected": summary["n_rejected"],
            "ttft_p50_ms": 1e3 * traffic.percentile(ttft, 50),
            "ttft_p95_ms": 1e3 * traffic.percentile(ttft, 95),
            "tpot_p50_ms": 1e3 * traffic.percentile(tpot, 50),
            "tpot_p95_ms": 1e3 * traffic.percentile(tpot, 95),
            "drain_s": run_s - args.seconds,
            "decode_step_ms": summary["decode_step_ms"],
            "slot_occupancy": summary["slot_occupancy"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
