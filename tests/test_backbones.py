"""Per-architecture smoke tests (assignment requirement): reduced same-family
config, one forward + one train step on CPU, asserting shapes + no NaNs;
plus prefill+decode == train-forward consistency (the serving path)."""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, get_smoke_config, get_config, cells, \
    skipped_cells
from repro.models import backbones as bb
from repro.models.config import SHAPES
from repro.algos.pg.ppo import make_lm_ppo_train_step
from repro.train.optim import adam

B, T = 2, 24


def _extras(cfg, rng):
    kw = {}
    if cfg.family == "vlm":
        kw["img"] = 0.1 * jax.random.normal(rng, (B, cfg.n_img_tokens,
                                                  cfg.d_model))
    if cfg.family == "encdec":
        kw["enc_frames"] = 0.1 * jax.random.normal(rng, (B, cfg.enc_len,
                                                         cfg.d_model))
    return kw


@pytest.mark.parametrize("aid", ARCH_IDS)
def test_smoke_forward_shapes_no_nan(aid, rng):
    cfg = get_smoke_config(aid)
    params = bb.init_lm(rng, cfg)
    tokens = jax.random.randint(rng, (B, T), 0, cfg.vocab)
    h, aux = bb.forward_train(params, tokens, cfg, **_extras(cfg, rng))
    logits = bb.lm_logits(params, h, cfg)
    value = bb.value_out(params, h)
    assert logits.shape == (B, T, cfg.padded_vocab)
    assert value.shape == (B, T)
    assert not bool(jnp.isnan(logits).any())
    assert not bool(jnp.isnan(value).any())


@pytest.mark.parametrize("aid", ARCH_IDS)
def test_smoke_train_step(aid, rng):
    cfg = get_smoke_config(aid)
    if cfg.n_experts:
        cfg = dataclasses.replace(cfg, capacity_factor=4.0)
    params = bb.init_lm(rng, cfg)
    opt = adam(1e-3, grad_clip=1.0)
    opt_state = opt.init(params)
    img_len = cfg.n_img_tokens if cfg.family == "vlm" else 0
    enc_len = cfg.enc_len if cfg.family == "encdec" else 0
    step = make_lm_ppo_train_step(cfg, opt, n_microbatches=2,
                                  img_len=img_len, enc_len=enc_len)
    batch = {
        "tokens": jax.random.randint(rng, (B, T), 0, cfg.vocab),
        "actions": jax.random.randint(rng, (B, T), 0, cfg.vocab),
        "logp_old": jnp.full((B, T), -3.0),
        "advantage": jax.random.normal(rng, (B, T)),
        "return_": jax.random.normal(rng, (B, T)),
    }
    if img_len:
        batch["img_embed"] = 0.1 * jax.random.normal(
            rng, (B, img_len, cfg.d_model))
    if enc_len:
        batch["enc_frames"] = 0.1 * jax.random.normal(
            rng, (B, enc_len, cfg.d_model))
    params2, opt_state2, metrics = jax.jit(step)(params, opt_state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    # params actually moved
    d = jax.tree_util.tree_map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                               params, params2)
    assert max(jax.tree_util.tree_leaves(d)) > 0


@pytest.mark.parametrize("aid", ARCH_IDS)
def test_prefill_decode_matches_train_forward(aid, rng):
    cfg = get_smoke_config(aid)
    if cfg.n_experts:  # dropless so serving is exact
        cfg = dataclasses.replace(cfg, capacity_factor=8.0)
    params = bb.init_lm(rng, cfg)
    tokens = jax.random.randint(rng, (B, T + 1), 0, cfg.vocab)
    kw = _extras(cfg, rng)
    h_all, _ = bb.forward_train(params, tokens, cfg, **kw)
    lg_train = bb.lm_logits(params, h_all, cfg)[:, T]
    cache = bb.init_cache(cfg, B, 64, img_len=cfg.n_img_tokens,
                          enc_len=cfg.enc_len)
    _, cache = bb.prefill(params, tokens[:, :T], cfg, cache, **kw)
    h_dec, cache = bb.decode_step(params, cache, tokens[:, T], cfg)
    lg_dec = bb.lm_logits(params, h_dec, cfg)[:, 0]
    scale = float(jnp.max(jnp.abs(lg_train))) + 1e-6
    err = float(jnp.max(jnp.abs(lg_train.astype(jnp.float32)
                                - lg_dec.astype(jnp.float32))))
    assert err / scale < 0.05, f"decode mismatch {err} vs scale {scale}"


def test_param_count_matches_analytic(rng):
    from repro.core.tree import tree_count_params
    for aid in ARCH_IDS:
        cfg = get_smoke_config(aid)
        params = bb.init_lm(rng, cfg)
        actual = tree_count_params(params)
        analytic = cfg.n_params() + cfg.d_model  # + value head
        assert abs(actual - analytic) / analytic < 0.02, (aid, actual, analytic)


def test_long_context_skips_documented():
    """The long_500k skip set matches DESIGN.md §Arch-applicability."""
    skipped = {a for a in ARCH_IDS if skipped_cells(a)}
    assert skipped == {"llama32_vision_90b", "qwen2_moe_a2p7b", "glm4_9b",
                       "granite_34b", "phi3_mini_3p8b", "whisper_medium"}
    for a in ARCH_IDS:
        names = [c.name for c in cells(a)]
        assert "train_4k" in names and "decode_32k" in names


# Leaves the forward reads in float32, which the compute-dtype tree keeps.
FLOAT32_READS = {"scale", "norm_scale", "A_log", "dt_bias", "value_head"}


@pytest.mark.parametrize("aid", ARCH_IDS)
def test_compute_weights_keeps_float32_reads_and_is_idempotent(aid, rng):
    cfg = get_smoke_config(aid)
    params = bb.init_lm(rng, cfg)
    once = bb.compute_weights(params, cfg)
    for path, leaf in jax.tree_util.tree_leaves_with_path(once):
        want = (jnp.float32 if path[-1].key in FLOAT32_READS
                else jnp.dtype(cfg.compute_dtype))
        assert leaf.dtype == want, (jax.tree_util.keystr(path), leaf.dtype)
    twice = bb.compute_weights(once, cfg)
    for a, b in zip(jax.tree_util.tree_leaves(once),
                    jax.tree_util.tree_leaves(twice)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("aid", ARCH_IDS)
def test_compute_weights_prefill_decode_logits_bitwise(aid, rng):
    """Prefill, one decode step and their logits, each compiled, read the
    compute-dtype tree to the same bits as the float32 tree: logits and
    every cache leaf."""
    cfg = get_smoke_config(aid)
    params = bb.init_lm(rng, cfg)
    tokens = jax.random.randint(rng, (B, 9), 0, cfg.vocab)
    kw = _extras(cfg, rng)

    @jax.jit
    def serve(p):
        cache = bb.init_cache(cfg, B, 16, img_len=cfg.n_img_tokens,
                              enc_len=cfg.enc_len)
        h, cache = bb.prefill(p, tokens[:, :8], cfg, cache, **kw)
        first = bb.lm_logits(p, h, cfg)
        h, cache2 = bb.decode_step(p, cache, tokens[:, 8], cfg)
        return first, cache, bb.lm_logits(p, h, cfg), cache2

    want = jax.tree_util.tree_leaves_with_path(serve(params))
    got = jax.tree_util.tree_leaves(serve(bb.compute_weights(params, cfg)))
    assert len(want) == len(got)
    for (path, w), g in zip(want, got):
        assert w.dtype == g.dtype, jax.tree_util.keystr(path)
        np.testing.assert_array_equal(np.asarray(w), np.asarray(g),
                                      err_msg=jax.tree_util.keystr(path))
