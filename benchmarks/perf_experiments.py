"""§Perf hillclimb driver: measure a cell with cfg overrides, print the
three roofline terms.  Usage:
  PYTHONPATH=src python -m benchmarks.perf_experiments A1 C1 B1
Keys map to (arch, shape, cfg_overrides) — see EXPERIMENTS.md §Perf."""
import json
import sys

from repro.models.config import SHAPES
from repro.launch.dryrun import force_host_devices, run_cell

CELLS = {c.name: c for c in SHAPES}

EXPERIMENTS = {
    # cell A: mamba2 train (memory-bound)
    "A0": ("mamba2-1.3b", "train_4k", {}, ""),
    "A1": ("mamba2-1.3b", "train_4k", {"ssd_bf16": True}, "ssd_bf16"),
    "A2": ("mamba2-1.3b", "train_4k",
           {"ssd_bf16": True, "ssd_chunk": 128}, "ssd_bf16_chunk128"),
    "A3": ("mamba2-1.3b", "train_4k",
           {"ssd_bf16": True, "ssd_chunk": 128, "cast_weights_bf16": True},
           "ssd_bf16_chunk128_cast"),
    "A4": ("mamba2-1.3b", "train_4k",
           {"ssd_bf16": True, "ssd_chunk": 64}, "ssd_bf16_chunk64"),
    # cell B: llama90b train (collective-bound)
    "B0": ("llama-3.2-vision-90b", "train_4k", {}, ""),
    "B1": ("llama-3.2-vision-90b", "train_4k", {"cast_weights_bf16": True},
           "castbf16"),
    # cell C: qwen2-moe decode (collective-bound, useful~0)
    "C0": ("qwen2-moe-a2.7b", "decode_32k", {}, ""),
    "C1": ("qwen2-moe-a2.7b", "decode_32k", {"decode_capacity_factor": 2.0},
           "cap2"),
    "C2": ("qwen2-moe-a2.7b", "decode_32k", {"decode_capacity_factor": 1.25},
           "cap1.25"),
}

if __name__ == "__main__":
    force_host_devices()
    for key in sys.argv[1:]:
        arch, shape, ov, tag = EXPERIMENTS[key]
        r = run_cell(arch, CELLS[shape], multi_pod=False, cfg_overrides=ov,
                     tag=tag or "base", save_dir="benchmarks/perf_results")
        roof = r["roofline"]
        print(f"== {key} {arch} {shape} {ov} ==")
        print(f"   compute={roof['t_compute_s']:.3e}s memory="
              f"{roof['t_memory_s']:.3e}s coll={roof['t_collective_s']:.3e}s "
              f"useful={r['useful_flops_ratio']:.3f}", flush=True)
