"""Kernel backend dispatch: which implementation serves each hot-path op.

Every Pallas kernel in this package has three runnable forms:

- ``ref``       — the pure-jnp reference math (XLA fuses it; this IS the
  baseline the roofline gate compares against).
- ``interpret`` — the Pallas kernel in interpret mode: the exact kernel
  program, executed as jax ops.  CPU-testable; used by CI to exercise the
  kernel code path on every PR.
- ``pallas``    — the compiled Mosaic kernel (TPU only).

Selection is per-op via the ``REPRO_KERNELS`` environment variable::

    REPRO_KERNELS=interpret                      # every op
    REPRO_KERNELS=attention=pallas,ssd=ref       # per-op
    REPRO_KERNELS=ref,sum_tree=interpret         # global default + override

or programmatically (tests, benches) with the :func:`override` context
manager.  The default is ``auto``: on a TPU backend, ops that won the
roofline gate (see ``GATE_WINNERS`` and ``benchmarks/BENCH_kernels.json``)
resolve to ``pallas``; everywhere else (and for gate losers) ``auto``
resolves to ``ref``.

Backend choice is read at TRACE time — code that flips backends must build
fresh jitted programs (the wired call sites do: every TrainLoop / train_step
closure re-reads the registry when it traces).
"""
from __future__ import annotations

import os
import threading
import warnings
from contextlib import contextmanager
from functools import lru_cache
from typing import Dict, Optional

OPS = ("attention", "ssd", "sum_tree")
BACKENDS = ("ref", "interpret", "pallas", "auto")
ENV = "REPRO_KERNELS"

# Roofline-gate verdicts (benchmarks/bench_kernels.py writes the evidence to
# benchmarks/BENCH_kernels.json): an op listed here beat the XLA baseline on
# every wired call-site's roofline table and becomes the compiled default
# under ``auto`` on TPU.  Ops absent here are demoted to reference-only:
# their kernels stay importable (and CI-exercised in interpret mode) but
# ``auto`` never selects them.
GATE_WINNERS = frozenset({"attention", "ssd", "sum_tree"})

_local = threading.local()


def _override_stack():
    if not hasattr(_local, "stack"):
        _local.stack = []
    return _local.stack


@lru_cache(maxsize=32)
def _parse(spec: str) -> Dict[str, str]:
    """``"interpret"`` / ``"attention=pallas,ssd=ref"`` -> {op: backend}.

    A bare token sets the default for every op; ``op=backend`` tokens
    override per-op.  Unknown ops/backends raise immediately — a typo'd env
    var must not silently fall back to the reference path.
    """
    out: Dict[str, str] = {}
    for tok in spec.split(","):
        tok = tok.strip()
        if not tok:
            continue
        if "=" in tok:
            op, _, be = tok.partition("=")
            op, be = op.strip(), be.strip()
            if op not in OPS:
                raise ValueError(f"{ENV}: unknown op {op!r} (ops: {OPS})")
            if be not in BACKENDS:
                raise ValueError(f"{ENV}: unknown backend {be!r} for {op!r}")
            out[op] = be
        else:
            if tok not in BACKENDS:
                raise ValueError(f"{ENV}: unknown backend {tok!r}")
            for op in OPS:
                out.setdefault(op, tok)
    return out


def _auto(op: str) -> str:
    import jax

    if jax.default_backend() == "tpu" and op in GATE_WINNERS:
        return "pallas"
    return "ref"


def backend_for(op: str, site: Optional[str] = None) -> str:
    """Resolved backend ('ref' | 'interpret' | 'pallas') for ``op``.

    ``site`` names the call site (e.g. ``"attention_train"``); when given,
    the resolution is reported as a ``kernel_dispatch`` telemetry event —
    resolution happens at TRACE time, so this records which backend each
    compiled program actually baked in, once per trace, not per step."""
    if op not in OPS:
        raise ValueError(f"unknown kernel op {op!r} (ops: {OPS})")
    be = "auto"
    env = os.environ.get(ENV, "")
    if env:
        be = _parse(env).get(op, "auto")
    for layer in _override_stack():
        if op in layer:
            be = layer[op]
    if be == "auto":
        be = _auto(op)
    if be == "interpret":
        import jax

        if jax.default_backend() != "cpu":
            warnings.warn(f"kernel op {op!r} runs in Pallas interpret mode "
                          f"on {jax.default_backend()!r}: a debugging path, "
                          f"not the compiled kernel", stacklevel=2)
    if site is not None:
        from ..telemetry import trace

        trace.emit("kernel_dispatch", f"{op}@{site}", op=op, site=site,
                   backend=be)
    return be


def resolve_interpret(op: str, interpret: Optional[bool]) -> bool:
    """Derive a kernel's ``interpret`` flag from the registry when the caller
    passed None: interpret everywhere except a resolved ``pallas`` backend.
    Direct kernel calls (tests, benches) therefore stay CPU-runnable by
    default instead of silently shipping interpret mode to compiled
    backends (the old hard-coded ``interpret=True``)."""
    if interpret is not None:
        return interpret
    return backend_for(op) != "pallas"


@contextmanager
def override(spec: str):
    """Scoped backend override, same syntax as the env var::

        with registry.override("interpret"):
            ...  # freshly-traced call sites dispatch to interpret kernels
    """
    _override_stack().append(_parse(spec))
    try:
        yield
    finally:
        _override_stack().pop()


def describe() -> Dict[str, str]:
    """Current resolved backend per op (for logs / --kernels echo)."""
    return {op: backend_for(op) for op in OPS}


def set_env(spec: str) -> None:
    """Install ``spec`` as the process-wide selection (validates first).
    Used by the launch drivers' ``--kernels`` flag; must run before any
    kernel call site is traced."""
    _parse(spec)  # validate
    os.environ[ENV] = spec
