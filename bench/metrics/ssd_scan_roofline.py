"""ssd_scan_roofline: the SSD kernel's share of its roofline, in %.

The least time the chip could take for one call (the larger of the SSD
op's minimal operations over peak and minimal bytes over peak, from its
shapes: bench/flops.py) times the kernel's calls in the trace, over the
kernel's device time in the trace.  Nothing is read where the kernel did
not run.  The kernel's device operation carries the name of the jitted
function that wraps its ``pallas_call`` (``_ssd_impl`` in
``kernels/ssd_scan/ops.py``): one per forward, and one more per remat
recomputation."""
from bench import flops, peaks

PATTERN = r"^%?_ssd_impl\b"


def read(run, trace):
    calls = trace.kernel_calls(PATTERN)
    seconds = trace.kernel_seconds(PATTERN)
    if not calls or seconds <= 0:
        return None
    r = run.record
    d = flops.mamba2_dims(r["model"])
    work = flops.ssd_op(r["batch"], r["horizon"], d["H"], d["P"], d["G"],
                        d["N"])
    t_min, _ = peaks.roofline_seconds(*work, run.device_kind)
    return 100.0 * t_min * calls / trace.n_devices / seconds
