"""Host-side tracing: spans, structured JSONL events, recompile detection,
device-memory snapshots.

The fused TrainLoop compiles whole log windows into single programs, so the
only places the host can observe are the seams between dispatches — this
module instruments exactly those seams:

- ``span("collect")``: a context manager that times a host phase, forwards
  the name to ``jax.profiler.TraceAnnotation`` (so the phase shows up on the
  perfetto timeline when ``--profile`` is active), and emits a structured
  JSONL event.  NOTE: wrapping an async jitted dispatch measures host-side
  dispatch time, not device compute — device compute lives in the profiler
  trace; the span tells you where the host thread went.
- recompile detection: jitted entry points registered via ``watch_jit`` are
  polled (``poll_recompiles``) for trace-cache growth; every newly compiled
  specialization emits a ``recompile`` event.  Silent retracing — a shape
  drifting per iteration, a weak-typed scalar flipping — is the classic
  fused-loop perf killer, and this is the counter that catches it.
- ``memory_snapshot``: per-device ``memory_stats()`` at phase boundaries
  (HBM growth across windows means a leaked buffer or an unexpected
  donation failure).  Backends without stats (CPU) skip silently.

Events are dicts with ``ts`` (unix seconds), ``kind``, ``name`` plus
kind-specific fields; they land in an in-memory ring (always, cheap) and —
when the tracer is configured with a path — one JSON object per line in a
``.jsonl`` file.  ``configure()`` installs the process-global tracer that
instrumented modules (TrainLoop, launch drivers, kernel registry) reach via
``get_tracer()``.
"""
from __future__ import annotations

import json
import os
import time
from collections import deque
from contextlib import contextmanager
from typing import Optional

import jax

RING_CAPACITY = 4096


class Tracer:
    """Event collector: ring buffer + optional JSONL file sink."""

    def __init__(self, path: Optional[str] = None,
                 ring_capacity: int = RING_CAPACITY):
        self.path = path
        self._file = None
        if path:
            os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
            self._file = open(path, "a", buffering=1)
        self.events: deque = deque(maxlen=ring_capacity)
        self._watched = {}      # name -> jitted callable
        self._cache_sizes = {}  # name -> last seen trace-cache size

    # -- events --------------------------------------------------------------
    def emit(self, kind: str, name: str, **fields) -> dict:
        event = {"ts": round(time.time(), 6), "kind": kind, "name": name,
                 **fields}
        self.events.append(event)
        if self._file is not None:
            self._file.write(json.dumps(event) + "\n")
        return event

    @contextmanager
    def span(self, name: str, **attrs):
        """Time a host phase; annotate the profiler timeline; emit a
        ``span`` event with ``dur_s`` on exit."""
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.emit("span", name, dur_s=round(time.perf_counter() - t0, 6),
                  **attrs)

    # -- recompilation detector ----------------------------------------------
    def watch_jit(self, name: str, fn) -> None:
        """Register a jitted entry point (anything ``jax.jit`` returns) for
        trace-cache-miss counting."""
        self._watched[name] = fn
        self._cache_sizes.setdefault(name, 0)

    def poll_recompiles(self) -> int:
        """Emit one ``recompile`` event per entry point whose trace cache
        grew since the last poll; returns the number of new compilations."""
        new_total = 0
        for name, fn in self._watched.items():
            n = fn._cache_size()
            prev = self._cache_sizes.get(name, 0)
            if n > prev:
                self.emit("recompile", name, cache_size=n, n_new=n - prev)
                new_total += n - prev
            self._cache_sizes[name] = n
        return new_total

    # -- device memory -------------------------------------------------------
    def memory_snapshot(self, tag: str) -> None:
        """One ``memory`` event per device that exposes memory_stats()
        (TPU/GPU; CPU returns None and is skipped)."""
        for d in jax.local_devices():
            stats = d.memory_stats()
            if not stats:
                continue
            self.emit("memory", tag, device=str(d),
                      bytes_in_use=stats.get("bytes_in_use"),
                      peak_bytes_in_use=stats.get("peak_bytes_in_use"),
                      bytes_limit=stats.get("bytes_limit"))

    def close(self) -> None:
        if self._file is not None:
            self._file.close()
            self._file = None


# -- process-global tracer ---------------------------------------------------
_global_tracer = Tracer()


def get_tracer() -> Tracer:
    return _global_tracer


def configure(path: Optional[str] = None) -> Tracer:
    """Install (and return) a fresh global tracer writing JSONL to ``path``.
    The previous tracer's file is closed; its ring is discarded."""
    global _global_tracer
    _global_tracer.close()
    _global_tracer = Tracer(path)
    return _global_tracer


def span(name: str, **attrs):
    """Module-level convenience: a span on the global tracer."""
    return _global_tracer.span(name, **attrs)


def emit(kind: str, name: str, **fields) -> dict:
    return _global_tracer.emit(kind, name, **fields)
