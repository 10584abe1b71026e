"""device_idle.lm_ppo: 1 - (union of device operation intervals) / traced
window, in %, averaged over the chips."""


def read(run, trace):
    return trace.idle_share()
