"""serve_queue_wait_p95_ms: 95th percentile (nearest rank) over every
request of the run of its wait in the scheduler's queue: from its due time
to the start of its admission, or to the run's end for a request the
scheduler rejected.  Read from the engine's ``serving.queue_wait_s``
samples on the process-global tracer; nothing where the engine records
none."""
from bench import traffic


def read(run, trace):
    from repro.telemetry.trace import get_tracer

    waits = getattr(get_tracer(), "samples", {}).get("serving.queue_wait_s")
    if not waits:
        return None
    return 1e3 * traffic.percentile(list(waits.values()), 95)
