"""serve_ttft_p95_ms: 95th percentile (nearest rank) over every request due
in the window of its first token's time minus its due time; a failed
request counts as its wait until the drain deadline."""
from bench import traffic


def read(run, trace):
    return 1e3 * traffic.percentile(run.record["ttft_s"], 95)
