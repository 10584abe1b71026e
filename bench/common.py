"""Plumbing shared by the harness and its jobs: paths, loading by name,
the run context a job fills in, and the compile cache.

Nothing here imports JAX at module level: the harness decides first whether
the program and a chip are there.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
import threading
import time
from contextlib import contextmanager

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# JAX's persistent compilation cache: a fixed path inside the checkout, so a
# cell's second run finds what its first run compiled
CACHE_DIR = os.path.join(ROOT, ".bench_cache", "jax")


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, bench: str = BENCH):
    """Import ``<bench>/<kind>/<name>.py`` (names may hold dots)."""
    path = os.path.join(bench, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def cell_spec(bm: dict, workload: str, root: str = ROOT) -> dict:
    """The cell ``workload`` with its configuration and traffic files read:
    {"cell", "config", "traffic", "end_to_end", "per_layer", "bench"}."""
    cells = {w["name"]: w for w in bm["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in bm["configs"]}
    config = load_json(os.path.join(root, configs[cell["config"]]["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     cell["traffic"] + ".json"))

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]

    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": [m for m in bm["end_to_end"] if mine(m)],
            "per_layer": [m for m in bm["per_layer"] if mine(m)],
            "bench": os.path.join(root, "bench")}


class Check:
    """One number that decides ``correct``: passes when ``value <= limit``."""

    def __init__(self, name: str, value: float, limit: float):
        self.name, self.value, self.limit = name, float(value), float(limit)

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit

    def as_json(self):
        return {"value": self.value, "limit": self.limit}


class Run:
    """What a job fills in during one run.

    The job calls :meth:`setup_done` right before its first timed
    operation, wraps the measured window in :meth:`window`, stores its raw
    counts in ``record``, calls :meth:`read_memory` once the window has
    closed and before it frees its state, and appends :class:`Check` s.
    """

    def __init__(self, *, t_process: float, seed: int, seconds: float,
                 trace: bool, trace_seconds: float, out_dir: str):
        self.t_process = t_process
        self.seed, self.seconds = seed, seconds
        self.trace, self.trace_seconds = trace, trace_seconds
        self.out_dir = out_dir
        self.setup_s = None
        self.record: dict = {}
        self.checks: list = []
        self.memory_peak_bytes = None
        self.profile_dir = None
        self.traced_window = None   # (t0, t1) host perf_counter of the trace
        self.device_kind, self.n_chips = None, None
        self._trace_lock = threading.Lock()
        self._timer = None

    def setup_done(self) -> None:
        self.setup_s = time.perf_counter() - self.t_process

    @contextmanager
    def window(self, *, timer: bool = False):
        """The measured window.  With tracing on, the profiler records its
        first ``trace_seconds``: the job calls :meth:`poll_trace` between
        dispatches, or, where it cannot (``timer``), a timer thread stops
        the trace."""
        if self.trace:
            import jax

            self.profile_dir = os.path.join(self.out_dir, "profile")
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0   # spans and runtime events only
            jax.profiler.start_trace(self.profile_dir,
                                     profiler_options=options)
            self._trace_t0 = time.perf_counter()
            if timer:
                self._timer = threading.Timer(self.trace_seconds,
                                              self.stop_trace)
                self._timer.start()
        try:
            yield
        finally:
            if self._timer is not None:
                self._timer.cancel()
                self._timer.join()
            self.stop_trace()

    def poll_trace(self) -> None:
        if (self.trace and self.traced_window is None
                and time.perf_counter() - self._trace_t0 >= self.trace_seconds):
            self.stop_trace()

    def stop_trace(self) -> None:
        with self._trace_lock:
            if self.trace and self.traced_window is None:
                import jax

                t1 = time.perf_counter()
                jax.profiler.stop_trace()
                self.traced_window = (self._trace_t0, t1)

    def read_memory(self) -> None:
        import jax

        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.local_devices()]
        self.memory_peak_bytes = int(max(peaks))


@contextmanager
def span(name: str):
    """A harness span: a ``bench.<name>`` annotation on the profiler's host
    timeline, which the trace reduction uses to attribute idle gaps."""
    import jax

    with jax.profiler.TraceAnnotation(f"bench.{name}"):
        yield


def enable_cache() -> None:
    """Keep JAX's persistent compilation cache inside the checkout and cache
    every program, however quick to compile, so set-up is the same work
    on every run after a cell's first."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def add_program_to_path(root: str = ROOT) -> None:
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise ImportError(f"the program is not in this checkout ({src})")
    if src not in sys.path:
        sys.path.insert(0, src)
