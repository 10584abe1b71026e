"""serve_admit_busy_share: the device's busy time inside the engine's
``serving.admit`` spans in the traced window, over those spans' length,
in %, averaged over the chips: how much of an admission is device work
and how much the host's dispatch between its programs.  Nothing where the
trace holds no such span, or no device."""
from bench import trace_reduce


def overlap_ns(a, b) -> int:
    """Length of the intersection of two sorted, disjoint interval lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def read(run, trace):
    admits = trace_reduce.merge([(s, e) for s, e, name in trace.host_spans
                                 if name == "serving.admit"])
    span_ns = sum(e - s for s, e in admits)
    if not span_ns or not trace.busy_by_device:
        return None
    busy_ns = sum(overlap_ns(busy, admits)
                  for busy in trace.busy_by_device.values())
    return 100.0 * busy_ns / trace.n_devices / span_ns
