"""The control (the plain reference with float8 weight products in the
program's place) reads far above the program, at a size the CPU holds, and
comes out not correct through the job's own comparison.  On the chip, at
the cells' own sizes, bench/calibrate.py reads both on a dozen seeds;
PERF.md gives the readings and the limits set from them."""
from bench import common
from bench.tests.smoke import smoke_spec


def _ratios(out):
    return {k: out["control"][k] / max(out["program"][k], 1e-12)
            for k in out["program"]}


def test_lm_ppo_control_reads_above_the_program():
    job = common.load_module("jobs", "lm_ppo")
    out = job.calibrate(smoke_spec("lmppo-mamba2-16L"), 2 ** 31 + 3)
    ratios = _ratios(out)
    assert max(ratios.values()) >= 3.0, out
    # the cell's own limits: the control fails the loss at this size too
    assert out["control_correct"] is False, out


def test_serve_control_reads_above_the_program():
    """At this size both gaps are far under the cell's limit, which was
    set from the full model's readings; a limit set by the same rule
    between this size's readings (program at most 0.0014, control at least
    0.013) shows the control come out not correct through compare()."""
    job = common.load_module("jobs", "serve")
    spec = smoke_spec("serve-mamba2-chat", limits={"served_token_gap": 0.005})
    out = job.calibrate(spec, 2 ** 31 + 4, seconds=1.5)
    assert out["control"]["served_token_gap"] >= \
        3.0 * out["program"]["served_token_gap"], out
    assert out["control_correct"] is False, out
    program = job.compare(out["program"]["served_token_gap"],
                          spec["traffic"]["limits"])
    assert all(c.ok for c in program), out
