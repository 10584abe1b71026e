"""The readers of the serving engine's spans, counters and samples, on a
hand-made tracer and a hand-made trace summary; each reads nothing where
the program traced nothing, as a program without these spans does."""
import types

import pytest

from bench import common
from bench import trace_reduce as tr


def reader(name):
    return common.load_module("metrics", name).read


@pytest.fixture
def tracer():
    from repro.telemetry import trace

    yield trace.configure(None)
    trace.configure(None)


NAMES = ["serve_queue_wait_p95_ms", "serve_tail_steps_per_admit",
         "serve_host_block_ms"]


@pytest.mark.parametrize("name", NAMES)
def test_reads_nothing_from_a_tracer_that_recorded_nothing(tracer, name):
    assert reader(name)(None, None) is None


@pytest.mark.parametrize("name", NAMES)
def test_reads_nothing_from_a_tracer_without_totals(monkeypatch, name):
    from repro.telemetry import trace

    monkeypatch.setattr(trace, "_global_tracer", types.SimpleNamespace())
    assert reader(name)(None, None) is None


def test_queue_wait_p95_is_the_nearest_rank(tracer):
    for rid in range(40):
        tracer.observe("serving.queue_wait_s", (40 - rid) / 100, key=rid)
    # 40 samples 0.01..0.40 s: rank ceil(0.95 * 40) = 38 -> 0.38 s
    assert reader("serve_queue_wait_p95_ms")(None, None) == pytest.approx(380)


def test_tail_steps_per_admit(tracer):
    tracer.count("serving.admitted", 4)
    tracer.count("serving.tail_steps", 13)
    assert reader("serve_tail_steps_per_admit")(None, None) == 3.25


def test_host_block_counts_self_seconds_per_decode_block(tracer):
    from repro.telemetry.trace import SpanTotal

    def total(count, seconds, self_seconds):
        t = SpanTotal()
        t.count, t.seconds, t.self_seconds = count, seconds, self_seconds
        return t

    tracer.totals["serving.decode"] = total(4, 0.4, 0.4)
    tracer.totals["serving.arrivals"] = total(5, 0.5, 0.002)
    tracer.totals["serving.bookkeeping"] = total(4, 0.006, 0.006)
    tracer.totals["serving.admit"] = total(2, 0.2, 0.01)   # not host loop
    assert reader("serve_host_block_ms")(None, None) == pytest.approx(2.0)


def _summary(host):
    ops = {"/device:TPU:0": [tr.DeviceOp("%prefill", 100, 200),
                             tr.DeviceOp("%advance", 260, 300),
                             tr.DeviceOp("%write", 300, 400),
                             tr.DeviceOp("%decode", 500, 900)]}
    return tr.TraceSummary(ops, host, window_s=1e-6)


def test_admit_busy_share_with_an_idle_gap_inside_the_admission():
    # the admission spans [90, 410): busy 100 + 140 of its 320 ns; the gap
    # [200, 260) lies inside it, under its tail-advance child
    s = _summary([(90, 410, "serving.admit"),
                  (200, 300, "serving.tail_advance"),
                  (420, 950, "serving.decode")])
    share = reader("serve_admit_busy_share")(None, s)
    assert share == pytest.approx(100 * 240 / 320)
    assert dict(s.idle_gaps())["serving.tail_advance"] == pytest.approx(60e-9)


def test_admit_busy_share_reads_nothing_without_admissions():
    s = _summary([(420, 950, "serving.decode")])
    assert reader("serve_admit_busy_share")(None, s) is None
    empty = tr.TraceSummary({}, [(0, 10, "serving.admit")], window_s=1e-6)
    assert reader("serve_admit_busy_share")(None, empty) is None


def test_a_traced_smoke_run_reports_the_engine_span_metrics():
    from bench.tests.smoke import run_smoke, smoke_spec

    res = run_smoke(smoke_spec("serve-mamba2-chat"), trace=1)
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert {"serve_queue_wait_p95_ms", "serve_tail_steps_per_admit",
            "serve_host_block_ms"} <= set(m)
    assert m["serve_queue_wait_p95_ms"] <= m["serve_ttft_p95_ms"]
    assert res["correct"] is True
