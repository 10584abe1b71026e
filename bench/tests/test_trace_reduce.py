"""The reduction from a profiler trace to busy time, device operations,
idle gaps and kernel time: on hand-made intervals, and on a small trace
recorded on a TPU v5e (``data/small_tpu.xplane.pb``: three rounds of a
matmul program, a readback and a 10 ms host sleep under ``bench.*``
spans, then a reduction program)."""
import os

import pytest

from bench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data", "small_tpu.xplane.pb")


def test_merge_unions_overlapping_intervals():
    assert tr.merge([(5, 7), (0, 2), (1, 3), (7, 8), (10, 11)]) == \
        [(0, 3), (5, 8), (10, 11)]
    assert tr.merge([]) == []


def _summary():
    ops = {"/device:TPU:0": [tr.DeviceOp("%while.3", 0, 150),
                             tr.DeviceOp("%fusion.1", 0, 100),
                             tr.DeviceOp("%my_kernel.2", 50, 150),
                             tr.DeviceOp("%fusion.1", 400, 500)],
           "/device:TPU:1": [tr.DeviceOp("%fusion.1", 0, 300)]}
    host = [(0, 1000, "bench.window"), (160, 390, "bench.readback")]
    return tr.TraceSummary(ops, host, window_s=1e-6)


def test_busy_idle_and_kernels_by_hand():
    s = _summary()
    # chip 0 busy [0,150) + [400,500) = 250 ns; chip 1 300 ns; mean 275 ns
    assert s.busy_s == pytest.approx(275e-9)
    assert s.idle_share() == pytest.approx(100 * (1 - 275 / 1000))
    assert s.kernel_seconds("my_kernel") == pytest.approx(100e-9 / 2)
    assert s.kernel_calls("fusion") == 3
    assert tr.op_name("%fusion.12 = f32[8]{0} fusion(f32[8] %p.1)") == \
        "%fusion.12"


def test_gaps_go_to_the_innermost_host_span():
    s = _summary()
    assert s.idle_gaps() == [["bench.readback", pytest.approx(250e-9 / 2)]]
    ops = dict(s.device_ops())
    assert ops["%fusion.1"] == pytest.approx(500e-9 / 2)
    assert "%while.3" not in ops           # a loop holds other operations


def test_recorded_tpu_trace():
    from jax.profiler import ProfileData

    with open(DATA, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    s = tr.summarize(data, window_s=1.0)
    assert s.n_devices == 1
    ops = s.ops_by_device["/device:TPU:0"]
    union = tr.merge([(o.start, o.end) for o in ops])
    assert s.busy_s == pytest.approx(sum(e - b for b, e in union) / 1e9)
    assert 0 < s.busy_s < 1.0
    gaps = dict(s.idle_gaps())
    # the longest idle stretch is the host's 10 ms sleep, three times over
    assert max(gaps, key=gaps.get) == "bench.host_sleep"
    assert gaps["bench.host_sleep"] == pytest.approx(0.03, rel=0.2)
