#!/usr/bin/env python3
"""Read the numbers that decide a cell's ``correct`` on many seeds, for the
program and for its control, in one process.

    python3 bench/calibrate.py --workload lmppo-mamba2-16L --seeds 1,2,3
    python3 bench/calibrate.py --workload serve-mamba2-chat --seeds 1,2 \
        --seconds 10

The control is the plain reference computed with float8 weight products
(the precision below the configuration's bfloat16) in the program's place.
Each seed prints one JSON line, with the control's verdict
(``control_correct``) from the job's own comparison against the limits in
the cell's traffic file.  The last line gives, for each number, the largest
program reading (the lower end of its limit) and the smallest control
reading (the upper end), and on how many seeds the control came out
correct.  A limit is set between the two, as PERF.md records; the
benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import common  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0,
                    help="serving: the window at the cell's load")
    ap.add_argument("--fault", default=None,
                    help="read the program with this fault planted "
                         "(the job's faults), and no control")
    args = ap.parse_args(argv)

    spec = common.cell_spec(common.benchmark(), args.workload)
    common.add_program_to_path()
    common.enable_cache()
    job = common.load_module("jobs", spec["traffic"]["job"])
    kw = {"seconds": args.seconds} if spec["traffic"]["job"] == "serve" \
        else {}
    if args.fault:
        kw["fault"] = args.fault
    lows, highs, control_correct = {}, {}, 0
    for seed in [int(s) for s in args.seeds.split(",")]:
        out = job.calibrate(spec, seed, **kw)
        print(json.dumps({"seed": seed, **out}), flush=True)
        for k, v in out["program"].items():
            lows[k] = max(lows.get(k, v), v)
        for k, v in out.get("control", {}).items():
            highs[k] = min(highs.get(k, v), v)
        control_correct += bool(out.get("control_correct"))
    print(json.dumps({"program_max": lows, "control_min": highs,
                      "control_correct_seeds": control_correct}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
