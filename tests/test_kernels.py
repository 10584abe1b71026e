"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode),
plus the backend dispatch seam: every wired call site (attention_train /
attention_decode, the SSD layer, DeviceReplay) run under ``ref`` vs
``interpret`` — forward AND gradients — and a fused-TrainLoop smoke test
under a global interpret override."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import registry
from repro.kernels.flash_attention import flash_attention, attention_reference
from repro.kernels.flash_attention.ops import flash_attention_decode
from repro.kernels.ssd_scan import ssd_scan, ssd_reference
from repro.kernels.sum_tree import (init_priorities, set_priorities,
                                    sample_reference)
from repro.kernels.sum_tree.sum_tree import sample_pallas


ATTN_CASES = [
    # B, T, S, H, Hkv, dh, causal, window, softcap, q_offset
    (2, 128, 128, 4, 2, 64, True, None, None, 0),
    (1, 256, 256, 8, 8, 128, True, None, None, 0),
    (2, 100, 100, 4, 1, 32, True, None, None, 0),
    (1, 128, 128, 4, 2, 64, True, 64, None, 0),
    (1, 128, 128, 4, 2, 64, True, None, 50.0, 0),
    (2, 64, 256, 4, 4, 64, True, None, None, 192),
    (1, 128, 96, 4, 2, 64, False, None, None, 0),
    (1, 64, 64, 2, 2, 16, True, 32, 30.0, 0),
]


@pytest.mark.parametrize("case", ATTN_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_vs_ref(case, dtype, rng):
    B, T, S, H, Hkv, dh, causal, window, softcap, qoff = case
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (B, T, H, dh), dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, dh), dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, dh), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          softcap=softcap, q_offset=qoff,
                          block_q=64, block_k=64)
    ref = attention_reference(q, k, v, causal=causal, window=window,
                              softcap=softcap, q_offset=qoff)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


SSD_CASES = [
    # B, T, H, P, G, N, chunk
    (2, 128, 8, 16, 1, 32, 32),
    (1, 64, 4, 64, 1, 128, 64),
    (2, 96, 8, 32, 2, 16, 32),
    (1, 256, 16, 64, 4, 64, 64),
    (1, 32, 2, 8, 1, 8, 16),
]


@pytest.mark.parametrize("case", SSD_CASES)
def test_ssd_scan_vs_ref(case, rng):
    B, T, H, P, G, N, chunk = case
    ks = jax.random.split(rng, 5)
    x = jax.random.normal(ks[0], (B, T, H, P), jnp.float32) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, T, G, N)) * 0.3
    Cm = jax.random.normal(ks[4], (B, T, G, N)) * 0.3
    y, s = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk)
    yr, sr = ssd_reference(x, dt, A, Bm, Cm, chunk=chunk)
    scale = float(jnp.max(jnp.abs(yr))) + 1e-9
    np.testing.assert_allclose(np.asarray(y) / scale, np.asarray(yr) / scale,
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), atol=2e-3)


def test_ssd_kernel_matches_backbone_math(rng):
    """Kernel output == the exact layers.ssd_chunked the backbones train with
    (same padding convention for ragged T)."""
    B, T, H, P, G, N = 2, 50, 4, 16, 1, 32
    ks = jax.random.split(rng, 5)
    x = jax.random.normal(ks[0], (B, T, H, P)) * 0.3
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.2)
    Bm = jax.random.normal(ks[3], (B, T, G, N)) * 0.3
    Cm = jax.random.normal(ks[4], (B, T, G, N)) * 0.3
    y, s = ssd_scan(x, dt, A, Bm, Cm, chunk=16)
    yr, sr = ssd_reference(x, dt, A, Bm, Cm, chunk=16)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-4)


SUMTREE_CASES = [(1024, 64, 256), (4096, 512, 128), (1000, 128, 64),
                 (64, 8, 32)]


@pytest.mark.parametrize("cap,bs,batch", SUMTREE_CASES)
def test_sum_tree_kernel_vs_ref(cap, bs, batch, rng):
    st = init_priorities(cap, bs)
    pr = jnp.abs(jax.random.normal(jax.random.PRNGKey(cap), (cap,))) + 0.01
    st = set_priorities(st, jnp.arange(cap), pr)
    tot = float(jnp.sum(pr))
    u = (jnp.arange(batch) + jax.random.uniform(rng, (batch,))) / batch * tot
    idx, prob = sample_pallas(st.leaves, st.block_sums, u,
                              block_b=min(64, batch))
    pr_pad = jnp.pad(pr, (0, st.leaves.size - cap))
    ridx, rprob = sample_reference(pr_pad, u)
    assert float(jnp.mean((idx == ridx).astype(jnp.float32))) > 0.995
    np.testing.assert_allclose(np.asarray(prob), np.asarray(rprob), atol=1e-5)


def test_flash_attention_equals_model_layer(rng):
    """Kernel == models/layers.multihead_attention (the train path)."""
    from repro.models.layers import multihead_attention
    B, T, H, Hkv, dh = 2, 64, 4, 2, 32
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (B, T, H, dh))
    k = jax.random.normal(ks[1], (B, T, Hkv, dh))
    v = jax.random.normal(ks[2], (B, T, Hkv, dh))
    out_kernel = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    out_layer = multihead_attention(q, k, v, q_positions=jnp.arange(T),
                                    k_positions=jnp.arange(T), causal=True,
                                    chunk_q=32)
    np.testing.assert_allclose(np.asarray(out_kernel), np.asarray(out_layer),
                               atol=3e-5)


# ---------------------------------------------------------------------------
# backend registry + dispatch seam
# ---------------------------------------------------------------------------

def _tree_max_diff(a, b):
    d = jax.tree_util.tree_map(
        lambda x, y: float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                           - y.astype(jnp.float32)))), a, b)
    return max(jax.tree_util.tree_leaves(d))


def test_registry_spec_parsing():
    with registry.override("interpret"):
        assert registry.backend_for("attention") == "interpret"
        assert registry.backend_for("ssd") == "interpret"
        with registry.override("attention=ref"):
            assert registry.backend_for("attention") == "ref"
            assert registry.backend_for("ssd") == "interpret"
    with registry.override("ref,sum_tree=interpret"):
        assert registry.backend_for("sum_tree") == "interpret"
        assert registry.backend_for("attention") == "ref"
    # auto on CPU -> ref; interpret defaults follow.  Overriding with
    # "auto" masks any REPRO_KERNELS set in the test environment (the CI
    # interpret leg runs this suite with REPRO_KERNELS=interpret).
    with registry.override("auto"):
        assert registry.backend_for("attention") == "ref"
        assert registry.resolve_interpret("attention", None) is True
        assert registry.resolve_interpret("attention", False) is False
    with pytest.raises(ValueError):
        registry.backend_for("conv")
    with pytest.raises(ValueError):
        with registry.override("attention=mosaic"):
            pass
    with pytest.raises(ValueError):
        with registry.override("flashattn=ref"):
            pass


def test_decode_op_kv_len_vs_ref(rng):
    """flash_attention_decode == reference with the per-batch valid-length
    mask, including a ragged (non-block-multiple) cache."""
    B, S, H, Hkv, dh = 3, 80, 4, 2, 32
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (B, 1, H, dh))
    k = jax.random.normal(ks[1], (B, S, Hkv, dh))
    v = jax.random.normal(ks[2], (B, S, Hkv, dh))
    kvl = jnp.array([1, 37, 80], jnp.int32)
    out = flash_attention_decode(q, k, v, kvl, block_k=32)
    ref = attention_reference(q, k, v, causal=False, kv_len=kvl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


ATTN_SITE_CFGS = [
    dict(d_model=64, n_heads=8, n_kv_heads=4, d_head=16, n_layers=1, vocab=64),
    dict(d_model=64, n_heads=4, n_kv_heads=4, d_head=16, n_layers=1, vocab=64,
         window=16, softcap_attn=30.0),
]


@pytest.mark.parametrize("ckw", ATTN_SITE_CFGS)
def test_attention_train_backend_parity(ckw, rng):
    """attention_train fwd + grads agree between ref and interpret backends
    (the custom_vjp path the fused PPO/A2C update compiles through)."""
    from repro.models.config import ModelConfig
    from repro.models import layers as L

    cfg = ModelConfig(**ckw)
    p = L.init_attention(rng, cfg)
    x = jax.random.normal(jax.random.fold_in(rng, 1), (2, 33, cfg.d_model))
    win = cfg.window

    def loss(p, x):
        y, _ = L.attention_train(p, x, cfg, window=win)
        return jnp.sum(y * y)

    outs = {}
    for spec in ("ref", "interpret"):
        with registry.override(spec):
            y, (k, v) = L.attention_train(p, x, cfg, window=win)
            g = jax.grad(loss, argnums=(0, 1))(p, x)
        outs[spec] = (y, k, v, g)
    assert _tree_max_diff(outs["ref"][0], outs["interpret"][0]) < 2e-5
    assert _tree_max_diff(outs["ref"][1], outs["interpret"][1]) == 0.0  # cache k
    assert _tree_max_diff(outs["ref"][3], outs["interpret"][3]) < 2e-4


@pytest.mark.parametrize("window", [None, 16])
def test_attention_decode_backend_parity(window, rng):
    """attention_decode (dense cache and rolling window buffer) agrees
    between the descent mask math and the kv_len kernel."""
    from repro.models.config import ModelConfig
    from repro.models import layers as L

    cfg = ModelConfig(d_model=64, n_heads=8, n_kv_heads=4, d_head=16,
                      n_layers=1, vocab=64)
    p = L.init_attention(rng, cfg)
    S = window or 24
    ck = jax.random.normal(jax.random.fold_in(rng, 1), (3, S, 4, 16)) * 0.1
    cv = jax.random.normal(jax.random.fold_in(rng, 2), (3, S, 4, 16)) * 0.1
    lengths = jnp.array([0, 7, S - 1])
    x = jax.random.normal(jax.random.fold_in(rng, 3), (3, 1, cfg.d_model))
    outs = {}
    for spec in ("ref", "interpret"):
        with registry.override(spec):
            outs[spec] = L.attention_decode(p, x, ck, cv, lengths, cfg,
                                            window=window)
    y0, k0, v0 = outs["ref"]
    y1, k1, v1 = outs["interpret"]
    assert _tree_max_diff(k0, k1) == 0.0 and _tree_max_diff(v0, v1) == 0.0
    assert _tree_max_diff(y0, y1) < 2e-5


def test_ssd_layer_backend_parity(rng):
    """ssd_block_train fwd + grads agree between ref and interpret (the
    mamba2/zamba2 train path through the custom_vjp)."""
    from repro.models.config import ModelConfig
    from repro.models import layers as L

    cfg = ModelConfig(d_model=64, n_layers=1, vocab=64, ssm_headdim=16,
                      ssm_n_groups=2, d_state=32, ssd_chunk=16)
    p = L.init_ssd(rng, cfg)
    u = jax.random.normal(jax.random.fold_in(rng, 1), (2, 40, cfg.d_model)) * 0.3

    def loss(p, u):
        y, _ = L.ssd_block_train(p, u, cfg)
        return jnp.sum(y * y)

    outs = {}
    for spec in ("ref", "interpret"):
        with registry.override(spec):
            y, (cst, sst) = L.ssd_block_train(p, u, cfg)
            g = jax.grad(loss, argnums=(0, 1))(p, u)
        outs[spec] = (y, sst, g)
    assert _tree_max_diff(outs["ref"][0], outs["interpret"][0]) < 2e-5
    assert _tree_max_diff(outs["ref"][1], outs["interpret"][1]) < 2e-5
    assert _tree_max_diff(outs["ref"][2], outs["interpret"][2]) < 2e-3


def test_device_replay_backend_parity(rng):
    """DeviceReplay insert / prioritized sample / update_priorities produce
    identical indices and weights under ref vs interpret (descent vs blocked
    kernel share exact smallest-cumsum-above-u semantics).  The trees agree
    to a few ulp: XLA may merge the blocked rebuild's level sums into wider
    reductions, so an internal node can be summed in another order."""
    from repro.replay import device as dreplay

    example = {"obs": jnp.zeros((4,)), "act": jnp.zeros((), jnp.int32)}
    outs = {}
    for spec in ("ref", "interpret"):
        with registry.override(spec):
            st = dreplay.init_replay(example, 100)
            for i in range(3):
                batch = {"obs": jnp.full((16, 4), float(i)),
                         "act": jnp.full((16,), i, jnp.int32)}
                st = dreplay.insert(st, batch,
                                    priorities=jnp.arange(1.0, 17.0) + i)
            _, idx, w = dreplay.sample(st, jax.random.fold_in(rng, 7), 32)
            st = dreplay.update_priorities(st, idx, jnp.linspace(0.1, 2.0, 32))
        outs[spec] = (st.tree, idx, w)
    assert bool(jnp.all(outs["ref"][1] == outs["interpret"][1]))
    np.testing.assert_allclose(np.asarray(outs["interpret"][0]),
                               np.asarray(outs["ref"][0]),
                               rtol=4 * np.finfo(np.float32).eps, atol=0)
    assert _tree_max_diff(outs["ref"][2], outs["interpret"][2]) == 0.0


def test_fused_trainloop_interpret_smoke(rng):
    """The scan-fused prioritized-DQN TrainLoop compiles and runs with every
    op on the interpret backend, and produces finite, shape-identical
    updates vs the ref run (sum-tree dispatch is bit-exact, so the whole
    window should agree)."""
    from repro.envs import make_env
    from repro.agents import make_dqn_agent
    from repro.algos import DQN
    from repro.models.rl_models import make_q_conv
    from repro.samplers import SerialSampler
    from repro.runners import OffPolicyRunner
    from repro.train.optim import adam

    class _Null:
        def record(self, *a, **k):
            pass

    def run_once(spec):
        with registry.override(spec):
            env = make_env("catch")
            model = make_q_conv(1, 3, img_hw=(10, 5), channels=(8,),
                                kernels=(3,), strides=(1,), d_out=32)
            agent = make_dqn_agent(model, 3)
            algo = DQN(model.apply, adam(1e-3), double=True,
                       target_update_interval=50)
            sampler = SerialSampler(env, agent, n_envs=4, horizon=8)
            runner = OffPolicyRunner(
                sampler, algo, logger=_Null(), fuse=True, replay_capacity=256,
                batch_size=32, updates_per_collect=2, min_replay=64,
                prioritized=True, n_iterations=4, log_interval=2,
                agent_state_kwargs={"epsilon": 0.2})
            ts, _, info = runner.run(rng)
        return ts, info

    ts_ref, info_ref = run_once("ref")
    ts_int, info_int = run_once("interpret")
    assert int(ts_int.step) == int(ts_ref.step) == 8
    assert np.isfinite(float(info_int.loss))
    ref_leaves = jax.tree_util.tree_leaves(ts_ref.params)
    int_leaves = jax.tree_util.tree_leaves(ts_int.params)
    assert [x.shape for x in ref_leaves] == [x.shape for x in int_leaves]
    assert all(bool(jnp.isfinite(x).all()) for x in int_leaves)
    assert _tree_max_diff(ts_ref.params, ts_int.params) < 1e-5


@pytest.mark.parametrize("aid", ["gemma2-2b", "mamba2-1.3b"])
def test_lm_train_step_interpret_finite(aid, rng):
    """LM-scale PPO train step (the launch/train.py path) under a global
    interpret override: compiles through the custom_vjp kernels and yields
    finite, shape-identical updates."""
    from repro.configs import get_smoke_config
    from repro.models import backbones as bb
    from repro.algos.pg.ppo import make_lm_ppo_train_step
    from repro.train.optim import adam

    cfg = get_smoke_config(aid)
    B, T = 2, 24
    params = bb.init_lm(rng, cfg)
    opt = adam(1e-3, grad_clip=1.0)
    opt_state = opt.init(params)
    batch = {
        "tokens": jax.random.randint(rng, (B, T), 0, cfg.vocab),
        "actions": jax.random.randint(rng, (B, T), 0, cfg.vocab),
        "logp_old": jnp.full((B, T), -3.0),
        "advantage": jax.random.normal(rng, (B, T)),
        "return_": jax.random.normal(rng, (B, T)),
    }
    with registry.override("interpret"):
        step = jax.jit(make_lm_ppo_train_step(cfg, opt))
        params2, _, metrics = step(params, opt_state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    d = jax.tree_util.tree_map(lambda a, b: a.shape == b.shape, params, params2)
    assert all(jax.tree_util.tree_leaves(d))
    assert all(bool(jnp.isfinite(x.astype(jnp.float32)).all())
               for x in jax.tree_util.tree_leaves(params2))
