"""serve_tokens_per_s: output tokens served per second while the window
was open: the tokens of every decode block read back by the window's
close, over the time from the window's start to the last such readback."""


def read(run, trace):
    r = run.record
    if not r["window_s"]:
        return None
    return r["window_tokens"] / r["window_s"]
