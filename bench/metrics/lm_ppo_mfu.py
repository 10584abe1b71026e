"""lm_ppo_mfu: model operations per second over the chips' peak, in %.

Per token: the rollout's forward and the update's forward and backward,
4 x the forward's operations (bench/flops.py); recomputation under remat
is not counted.  The rate is that of the traced window: the steps of the
window calls that completed inside it, over the time from the first of
those completions to the last."""
from bench import flops, peaks


def read(run, trace):
    r = run.record
    t0, t1 = run.traced_window
    ends = [t for t in r["call_ends"] if t0 <= t <= t1]
    if len(ends) < 2:
        return None
    per_step = flops.lm_ppo_step_flops(r["model"], r["padded_vocab"],
                                       r["batch"], r["horizon"])
    rate = per_step * r["fuse_window"] * (len(ends) - 1) / (ends[-1] - ends[0])
    return 100.0 * rate / (run.n_chips * peaks.peak(run.device_kind)["flops"])
