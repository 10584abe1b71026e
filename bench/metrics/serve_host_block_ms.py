"""serve_host_block_ms: the host's own time between decode blocks, per
block: the self seconds of the engine's ``serving.arrivals`` and
``serving.bookkeeping`` spans (submitting due requests; attributing tokens,
retiring slots, polling for recompiles, splitting the next block's key)
over the count of its
``serving.decode`` spans, from the process-global tracer's per-name totals;
nothing where the engine traced no decode block."""

HOST = ("serving.arrivals", "serving.bookkeeping")


def read(run, trace):
    from repro.telemetry.trace import get_tracer

    totals = getattr(get_tracer(), "totals", {})
    decode = totals.get("serving.decode")
    if decode is None or not decode.count:
        return None
    host_s = sum(totals[n].self_seconds for n in HOST if n in totals)
    return 1e3 * host_s / decode.count
