"""serve_tail_steps_per_admit: one-token tail steps per admitted request
(each prompt's length less the largest prefill bucket at or below it),
from the engine's ``serving.tail_steps`` and ``serving.admitted`` counters
on the process-global tracer; nothing where the engine counts none."""


def read(run, trace):
    from repro.telemetry.trace import get_tracer

    counters = getattr(get_tracer(), "counters", {})
    admitted = counters.get("serving.admitted", 0)
    if not admitted:
        return None
    return counters.get("serving.tail_steps", 0) / admitted
