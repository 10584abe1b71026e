"""serve_admit_ms: the engine's host-clocked prefill seconds (bucket
prefill, tail advance, slot write, each ended by block_until_ready) per
admitted request."""


def read(run, trace):
    r = run.record
    return 1e3 * r["prefill_s"] / r["admitted"] if r["admitted"] else None
