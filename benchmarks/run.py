"""Benchmark harness: one bench module per paper table/figure.

  Fig 1 / §2.1 + §3.2 SPS  -> bench_samplers
  §1.1 replay options      -> bench_replay
  Figs 4-6 learning curves -> bench_learning (curves in benchmarks/curves/)
  Fig 7-8 R2D1 pipeline    -> bench_r2d1
  LM serving (Fig 1 at LM scale) -> bench_serving
  §Perf GAE lowering       -> bench_gae
  Kernel roofline gate     -> bench_kernels (BENCH_kernels.json)
  Sentinel overhead gate   -> bench_telemetry (BENCH_telemetry.json)
  §2.3 async vs sync SPS   -> bench_async (BENCH_async.json)

Roofline terms come from the dry-run (benchmarks/dryrun_results/ via
python -m repro.launch.dryrun), not from CPU wall time.

Prints ``name,us_per_call,derived`` CSV.
"""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    from . import (bench_samplers, bench_replay, bench_gae, bench_serving,
                   bench_learning, bench_r2d1, bench_kernels, bench_telemetry,
                   bench_async)
    mods = [("samplers", bench_samplers), ("replay", bench_replay),
            ("gae", bench_gae), ("serving", bench_serving),
            ("learning", bench_learning), ("r2d1", bench_r2d1),
            ("kernels", bench_kernels), ("telemetry", bench_telemetry),
            ("async", bench_async)]
    if len(sys.argv) > 1:
        only = set(sys.argv[1:])
        mods = [(n, m) for n, m in mods if n in only]
    print("name,us_per_call,derived")
    failed = 0
    for name, mod in mods:
        try:
            for row in mod.run():
                print(f"{row['name']},{row['us_per_call']},{row['derived']}",
                      flush=True)
        except Exception as e:
            failed += 1
            print(f"{name},ERROR,{type(e).__name__}", flush=True)
            traceback.print_exc(file=sys.stderr)
    if failed:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
