"""setup_s: process start to the first timed operation (host clock)."""


def read(run, trace):
    return run.setup_s
