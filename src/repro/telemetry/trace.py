"""Host-side tracing: spans, structured JSONL events, per-name totals,
counters and samples, recompile detection, device-memory snapshots.

The fused TrainLoop compiles whole log windows into single programs, so the
only places the host can observe are the seams between dispatches — this
module instruments exactly those seams:

- ``span("collect", **attrs)``: a context manager that times a host phase.
  Each span gets an ``id`` and the ``parent`` id of the span open around it
  on the same thread, and ``start``/``end`` on ``time.perf_counter``.  It
  opens a ``jax.profiler.TraceAnnotation`` of the same name that carries
  the id, the parent and the attributes, so a profiler event joins its
  in-memory record by id, on the profiler's clock beside the device
  planes.  NOTE: wrapping an async jitted dispatch measures host-side
  dispatch time, not device compute — device compute lives in the profiler
  trace; the span tells you where the host thread went.
- per-name totals (``totals[name]``: count, seconds, self seconds, which
  leave out the child spans), named counters (``count``) and per-name
  samples (``observe``).  They hold every span, count and sample since the
  tracer was made, however many events the ring has dropped.
- recompile detection: jitted entry points registered via ``watch_jit`` are
  polled (``poll_recompiles``) for trace-cache growth; every newly compiled
  specialization emits a ``recompile`` event.  Silent retracing — a shape
  drifting per iteration, a weak-typed scalar flipping — is the classic
  fused-loop perf killer, and this is the counter that catches it.
- ``memory_snapshot``: per-device ``memory_stats()`` at phase boundaries
  (HBM growth across windows means a leaked buffer or an unexpected
  donation failure).  Backends without stats (CPU) skip silently.

Events are dicts with ``ts`` (unix seconds), ``kind``, ``name`` plus
kind-specific fields; they land in an in-memory ring of the last
``ring_capacity`` (always, cheap) and — when the tracer is configured with
a path — one JSON object per line in a ``.jsonl`` file.  ``configure()``
installs a fresh process-global tracer (empty ring, totals, counters and
samples) that instrumented modules (TrainLoop, launch drivers, the serving
engine, kernel registry) reach via ``get_tracer()``.
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time
from collections import defaultdict, deque
from contextlib import contextmanager
from typing import Dict, Optional

import jax

RING_CAPACITY = 4096


class SpanTotal:
    """Every span of one name: how many, their seconds, and their self
    seconds (each span's duration less that of its child spans)."""

    __slots__ = ("count", "seconds", "self_seconds")

    def __init__(self):
        self.count, self.seconds, self.self_seconds = 0, 0.0, 0.0


class Tracer:
    """Event collector: ring buffer + optional JSONL file sink, with
    per-name span totals, counters and samples beside the ring."""

    def __init__(self, path: Optional[str] = None,
                 ring_capacity: int = RING_CAPACITY):
        self.path = path
        self._file = None
        if path:
            os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
            self._file = open(path, "a", buffering=1)
        self.events: deque = deque(maxlen=ring_capacity)
        self.totals: Dict[str, SpanTotal] = defaultdict(SpanTotal)
        self.counters: Dict[str, int] = defaultdict(int)
        self.samples: Dict[str, dict] = defaultdict(dict)  # name -> key -> value
        self._lock = threading.Lock()   # spans and counts come from threads
        self._ids = itertools.count(1)
        self._open = threading.local()  # per thread: stack of [id, child s]
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
        """Time a host phase; annotate the profiler timeline with its id,
        parent and ``attrs``; add it to ``totals[name]`` and emit a ``span``
        event (``id``, ``parent``, ``start``, ``end``, ``dur_s``, attrs) on
        exit.  Yields the attribute dict: what the body adds to it, known
        only at the phase's end, goes out with the event and the
        annotation."""
        stack = getattr(self._open, "stack", None)
        if stack is None:
            stack = self._open.stack = []
        sid = next(self._ids)
        parent = stack[-1][0] if stack else None
        frame = [sid, 0.0]   # id, seconds of the child spans
        stack.append(frame)
        ids = {"id": sid} if parent is None else {"id": sid, "parent": parent}
        n_given = len(attrs)
        start = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name, **ids, **attrs) as ann:
                yield attrs
                if len(attrs) > n_given:
                    ann.set_metadata(**dict(list(attrs.items())[n_given:]))
        finally:
            end = time.perf_counter()
            stack.pop()
            dur = end - start
            if stack:
                stack[-1][1] += dur
            with self._lock:
                tot = self.totals[name]
                tot.count += 1
                tot.seconds += dur
                tot.self_seconds += dur - frame[1]
            self.emit("span", name, id=sid, parent=parent, start=start,
                      end=end, dur_s=round(dur, 6), **attrs)

    def span_seconds(self, name: str) -> float:
        """Total seconds of every ``name`` span so far."""
        tot = self.totals.get(name)
        return tot.seconds if tot is not None else 0.0

    def count(self, name: str, n: int = 1) -> None:
        """Add ``n`` to the counter ``name``."""
        with self._lock:
            self.counters[name] += n

    def observe(self, name: str, value: float, key) -> None:
        """Record the sample ``key`` of ``name``; a later sample under the
        same key replaces it."""
        with self._lock:
            self.samples[name][key] = value

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
    The previous tracer's file is closed; its ring, totals, counters and
    samples are discarded."""
    global _global_tracer
    _global_tracer.close()
    _global_tracer = Tracer(path)
    return _global_tracer


def span(name: str, **attrs):
    """Module-level convenience: a span on the global tracer."""
    return _global_tracer.span(name, **attrs)


def emit(kind: str, name: str, **fields) -> dict:
    return _global_tracer.emit(kind, name, **fields)
