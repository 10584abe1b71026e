"""serve_decode_block_ms: the engine's decode seconds per decode block,
dispatch to the tokens' readback on the host."""


def read(run, trace):
    r = run.record
    return r["decode_step_ms"] * r["decode_block"]
