"""serve_tpot_p95_ms: 95th percentile (nearest rank) over requests of
(last token - first token) / (tokens - 1); a failed request counts as its
wait until the drain deadline."""
from bench import traffic


def read(run, trace):
    return 1e3 * traffic.percentile(run.record["tpot_s"], 95)
