"""The LM-PPO job runs at smoke size on the CPU through the harness, and
its check comes out false when the timed path is broken."""
import csv

import numpy as np

from bench import common
from bench.tests.smoke import run_smoke, smoke_spec

CELL = "lmppo-mamba2-16L"


def test_lm_ppo_runs_and_reports_its_metrics():
    res = run_smoke(smoke_spec(CELL))
    assert set(res["metrics"]) == {"lm_ppo_tokens_per_s", "setup_s"}
    assert res["metrics"]["lm_ppo_tokens_per_s"]["value"] > 0
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["checks"]) == {"rollout_logp_mean_gap", "loss_rel_gap",
                                  "adam_mu_norm_gap", "param_change_norm_gap"}
    assert res["correct"] is True
    assert list(res)[-1] == "checks"


def test_lm_ppo_unchanged_state_is_not_correct():
    res = run_smoke(smoke_spec(CELL), fault="unchanged")
    assert res["correct"] is False
    assert res["checks"]["param_change_norm_gap"]["value"] > 0.99


def test_lm_ppo_half_batch_is_not_correct():
    res = run_smoke(smoke_spec(CELL), fault="half_batch")
    assert res["correct"] is False


def test_lm_ppo_altered_token_is_not_correct():
    res = run_smoke(smoke_spec(CELL), fault="altered_token")
    assert res["correct"] is False


def test_window_copy_matches_the_programs_window(tmp_path):
    """The job times a copy of launch/train.py's fused window; on the same
    seed and sizes both give the same loss at each step and the same
    parameters after the window."""
    import jax
    from repro.envs.token_lm import make_token_lm
    from repro.launch import train
    from repro.models import backbones as bb
    from repro.runners.train_loop import split_keys

    spec = smoke_spec(CELL)
    tr, job = spec["traffic"], common.load_module("jobs", "lm_ppo")
    cfg = job.model_config(spec)
    losses = []
    for steps in (1, 2):
        log_dir = tmp_path / str(steps)
        theirs = train.run(train.parse_args([
            "--steps", str(steps), "--fuse-window", "2", "--seed", "5",
            "--batch", str(tr["batch"]), "--horizon", str(tr["horizon"]),
            "--lr", str(tr["lr"]), "--log-dir", str(log_dir)]), cfg)
        with open(log_dir / "progress.csv") as f:
            losses.append(float(list(csv.DictReader(f))[-1]["loss"]))

    k_init, rng = jax.random.split(jax.random.PRNGKey(5))
    params = bb.init_lm(k_init, cfg)
    env = make_token_lm(vocab=cfg.vocab, episode_len=tr["horizon"])
    opt, window = job.build_window(cfg, env, tr)
    _, ks = split_keys(rng, 2)
    mine, _, out = window(params, opt.init(params), ks)
    np.testing.assert_allclose(np.asarray(out["loss"]), losses, rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(mine),
                    jax.tree_util.tree_leaves(theirs)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
