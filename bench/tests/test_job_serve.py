"""The serving job runs at smoke size on the CPU through the harness, and
its check comes out false when a served token is altered."""
from bench.tests.smoke import run_smoke, smoke_spec

CELL = "serve-mamba2-chat"


def test_serve_runs_and_reports_its_metrics():
    res = run_smoke(smoke_spec(CELL))
    assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert res["metrics"]["serve_tokens_per_s"]["value"] > 0
    assert res["attempted"] >= 4
    assert set(res["checks"]) == {"served_token_gap"}
    assert res["correct"] is True


def test_serve_traced_run_reads_the_engine_layers():
    res = run_smoke(smoke_spec(CELL), trace=1)
    assert {"serve_admit_ms", "serve_decode_block_ms", "serve_ttft_p95_ms",
            "serve_tpot_p95_ms"} <= set(res["metrics"])
    assert "window_s" in res["device"] and "breakdown" in res


def test_serve_altered_token_is_not_correct():
    res = run_smoke(smoke_spec(CELL), fault="altered_token")
    assert res["correct"] is False
