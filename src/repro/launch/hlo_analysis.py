"""Roofline terms from a compiled dry-run artifact.

``cost_analysis()`` supplies HLO FLOPs and bytes accessed; collective bytes
are NOT in cost_analysis, so we parse the post-SPMD optimized HLO text and
sum operand sizes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute, converting to per-device wire bytes with
ring-algorithm factors and the replica-group size.
"""
from __future__ import annotations

import re
from typing import Dict

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"\b(pred|s8|u8|s16|u16|bf16|f16|s32|u32|f32|s64|u64|f64|c64|c128)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    m = _GROUPS_IOTA_RE.search(line)  # e.g. replica_groups=[32,16]<=[512]
    if m:
        return int(m.group(2))
    return 1


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-device wire bytes by collective kind (ring factors applied).

    NOTE: instructions inside while bodies are counted ONCE by this text
    walk — the dry-run therefore measures collectives on UNROLLED
    1/2-superblock cost variants and extrapolates (dryrun.py), never relying
    on this parse for a scanned module."""
    out = {k: 0.0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        if " = " not in s:
            continue
        kind = None
        for k in _COLLECTIVES:
            if f" {k}(" in s or f" {k}-start(" in s:
                kind = k
                break
        if kind is None:
            continue
        # Post-SPMD HLO prints per-device RESULT shapes but not operand
        # shapes; derive the wire bytes from the result and group size g:
        #   all-gather:     operand = result/g -> wire = result*(g-1)/g
        #   all-reduce:     operand = result   -> wire = 2*result*(g-1)/g
        #   reduce-scatter: operand = result*g -> wire = result*(g-1)
        #   all-to-all:     operand = result   -> wire = result*(g-1)/g
        #   collective-permute:                   wire = result
        head = s.split(f" {kind}(")[0].split(f" {kind}-start(")[0]
        shapes = _SHAPE_RE.findall(head)
        if not shapes:
            continue
        res_bytes = float(sum(_shape_bytes(d, dim) for d, dim in shapes))
        g = max(_group_size(s), 1)
        if kind == "all-gather":
            wire = res_bytes * (g - 1) / g
        elif kind == "all-reduce":
            wire = 2.0 * res_bytes * (g - 1) / g
        elif kind == "reduce-scatter":
            wire = res_bytes * (g - 1)
        elif kind == "all-to-all":
            wire = res_bytes * (g - 1) / g
        else:  # collective-permute
            wire = res_bytes
        out[kind] += wire
        counts[kind] += 1
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    out["counts"] = counts
    return out


def xla_cost(fn, *args, **kwargs) -> Dict[str, float]:
    """FLOPs / bytes-accessed of ``fn`` jit-compiled at these args — the XLA
    baseline side of the kernel roofline gate (benchmarks/bench_kernels.py).
    Works on CPU: cost_analysis reflects the optimized HLO of whatever
    backend compiles it, which is what the pure-jnp reference would run."""
    import jax

    c = jax.jit(fn).lower(*args, **kwargs).compile().cost_analysis()
    if isinstance(c, (list, tuple)):
        c = c[0] if c else {}
    return {"flops": float(c.get("flops", 0.0)),
            "bytes accessed": float(c.get("bytes accessed", 0.0))}


# Published per-chip peaks, keyed by jax ``Device.device_kind``.  Source:
# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM,
# 1,600 Gbit/s of chip-to-chip interconnect over 4 links (50 GB/s each).
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bw": 819e9,
                    "ici_link_bw": 50e9},
}


def device_peaks(device_kind: str) -> dict:
    """Peak rates of one chip of ``device_kind``; an unknown kind is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add it to hlo_analysis.PEAKS (known: "
                       f"{sorted(PEAKS)})") from None


def roofline_terms(cost: dict, coll: dict, n_chips: int, *,
                   device_kind: str) -> dict:
    """Three roofline terms in seconds on ``device_kind``'s peaks."""
    peaks = device_peaks(device_kind)
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    cbytes = float(coll.get("total", 0.0))
    # cost_analysis of the SPMD-partitioned module is already per-device.
    t_compute = flops / peaks["flops_bf16"]
    t_memory = byts / peaks["hbm_bw"]
    t_collective = cbytes / peaks["ici_link_bw"]
    dom = max((("compute", t_compute), ("memory", t_memory),
               ("collective", t_collective)), key=lambda kv: kv[1])[0]
    return {
        "flops_per_device": flops,
        "bytes_per_device": byts,
        "collective_bytes_per_device": cbytes,
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_collective,
        "bottleneck": dom,
    }
