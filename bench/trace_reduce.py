"""Reduce a JAX profiler trace (``.xplane.pb``) to the device metrics.

- busy: the union of the intervals in which an operation ran on a device
  (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), averaged over
  the devices traced;
- device operations by total time, grouped by name;
- idle gaps between busy intervals, each attributed to the innermost host
  span that covers its middle: the harness's ``bench.*`` spans, the
  program's own ``TraceAnnotation`` s, or the runtime's host events;
- kernel time: the summed device time of the operations whose name
  matches a pattern.

A device operation's name is its HLO instruction's name (``%fusion.12``,
``%_ssd_impl.13``), cut from the full instruction text the trace carries.
Loops and calls (``%while.4``) contain other operations: they count
towards busy time but not among the operations listed by time.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
CONTAINER = re.compile(r"^%?(while|conditional|call)[.\d]*$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
TOP = 10


def find_xplane(profile_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(profile_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    return paths[-1]


def merge(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Union of [start, end) intervals, sorted and disjoint."""
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


class DeviceOp:
    __slots__ = ("name", "start", "end")

    def __init__(self, name, start, end):
        self.name, self.start, self.end = name, start, end


class TraceSummary:
    """What the metric readers read from one traced window."""

    def __init__(self, ops_by_device: Dict[str, List[DeviceOp]],
                 host_spans: List[Tuple[int, int, str]], window_s: float):
        self.ops_by_device = ops_by_device
        self.host_spans = host_spans
        self.window_s = float(window_s)
        self.n_devices = max(len(ops_by_device), 1)
        self.busy_by_device = {d: merge([(o.start, o.end) for o in ops])
                               for d, ops in ops_by_device.items()}
        busy_ns = sum(e - s for b in self.busy_by_device.values()
                      for s, e in b)
        self.busy_s = busy_ns / self.n_devices / 1e9

    # -- what the metric readers use -----------------------------------------
    def idle_share(self) -> Optional[float]:
        """1 - busy / window, as a percentage."""
        if self.window_s <= 0 or self.busy_s <= 0:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def matching(self, pattern: str) -> List[DeviceOp]:
        rx = re.compile(pattern)
        return [o for ops in self.ops_by_device.values() for o in ops
                if rx.search(o.name)]

    def kernel_seconds(self, pattern: str) -> float:
        """Device seconds of the operations matching ``pattern``, summed
        over devices and averaged per device."""
        return sum(o.end - o.start for o in self.matching(pattern)) \
            / self.n_devices / 1e9

    def kernel_calls(self, pattern: str) -> int:
        return len(self.matching(pattern))

    def device_ops(self) -> List[List]:
        """The device operations that took most time, by name."""
        tot = defaultdict(int)
        for ops in self.ops_by_device.values():
            for o in ops:
                if not CONTAINER.match(o.name):
                    tot[o.name] += o.end - o.start
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:TOP]
        return [[name, ns / self.n_devices / 1e9] for name, ns in top]

    def idle_gaps(self) -> List[List]:
        """Idle time between busy intervals, summed by the host span that
        covers each gap's middle (innermost span wins)."""
        tot = defaultdict(int)
        spans = sorted(self.host_spans, key=lambda s: s[1] - s[0])
        for busy in self.busy_by_device.values():
            for (_, e0), (s1, _) in zip(busy, busy[1:]):
                mid = (e0 + s1) // 2
                name = next((n for s, e, n in spans if s <= mid < e),
                            "no host span")
                tot[name] += s1 - e0
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:TOP]
        return [[name, ns / self.n_devices / 1e9] for name, ns in top]

    def breakdown(self) -> dict:
        return {"device_ops": self.device_ops(),
                "idle_gaps": self.idle_gaps()}


def op_name(text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``%fusion.12``."""
    return text.split(" = ", 1)[0].strip()


def reduce(profile_dir: str, *, window_s: float) -> TraceSummary:
    """Read the newest trace under ``profile_dir``; ``window_s`` is the
    traced window's length by the host clock."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(profile_dir))
    return summarize(data, window_s=window_s)


def summarize(data, *, window_s: float) -> TraceSummary:
    ops_by_device: Dict[str, List[DeviceOp]] = {}
    host_spans: List[Tuple[int, int, str]] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    start = int(ev.start_ns)
                    ops.append(DeviceOp(op_name(ev.name), start,
                                        start + int(ev.duration_ns)))
            ops_by_device[plane.name] = ops
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns > 0:
                        start = int(ev.start_ns)
                        host_spans.append((start, start + int(ev.duration_ns),
                                           ev.name))
    return TraceSummary(ops_by_device, host_spans, window_s)
