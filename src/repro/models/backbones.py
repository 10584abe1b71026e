"""Backbone zoo: one train / prefill / decode implementation per family.

The agent's "Model" (paper §6.1) at modern scale.  All backbones share:

- params: nested dicts of fp32 leaves; layer stacks carry a leading
  superblock dim and are consumed by ``lax.scan`` (HLO size independent of
  depth; heterogeneous depth patterns scan over *superblocks*).
- forward_train(params, tokens) -> (hidden, aux): full-sequence compute,
  activations bf16, optional remat per superblock, residual stream sharded
  (data, model-on-seq) for sequence-parallel activation memory.
- prefill / decode_step: serving path with explicit cache namedarraytuple-style
  dicts (KV rolling buffers for sliding-window layers, SSM conv+state for
  mamba, cross-KV for vlm/encdec).  decode_step is the paper's batched
  action-selection: one token for every sequence in the batch.

Families: dense (glm4/granite/phi3), dense-alt (gemma2 local/global + softcaps),
moe (qwen2-moe/mixtral), ssm (mamba2), hybrid (zamba2), vlm (llama-3.2-vision),
encdec (whisper).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .config import ModelConfig
from . import sharding as shd
from .layers import (
    F32,
    scan_or_unroll,
    cdtype,
    init_rmsnorm,
    rmsnorm,
    init_attention,
    attention_train,
    attention_decode,
    cross_attention_decode,
    init_mlp,
    mlp,
    init_moe,
    moe,
    init_ssd,
    ssd_block_train,
    ssd_block_decode,
    apply_rope,
    multihead_attention,
    _dense_init,
)

# ---------------------------------------------------------------------------
# Activation sharding helpers
# ---------------------------------------------------------------------------

def _scan(cfg, body, carry, xs):
    """lax.scan over stacked superblocks, or an unrolled python loop when
    cfg.unroll (dry-run cost variants — see layers.scan_or_unroll)."""
    return scan_or_unroll(body, carry, xs, cfg.unroll)


def _res_spec(seq_shard: bool = True) -> P:
    """Residual stream (B, T, D): batch over dp axes; seq over tp axis
    (sequence-parallel activations — Megatron-SP adapted to pjit)."""
    return P(shd.dp_axes(), shd.tp_axis() if seq_shard else None, None)


def constrain_res(x, cfg: ModelConfig):
    T = x.shape[1]
    tp = shd.tp_size()
    if tp > 1 and T % tp == 0 and T >= tp:
        return shd.constrain(x, _res_spec(True))
    return shd.constrain(x, _res_spec(False))


# ---------------------------------------------------------------------------
# Superblock layout per family
# ---------------------------------------------------------------------------

def superblock_layout(cfg: ModelConfig):
    """Returns (n_superblocks, layers_per_block, tail_layers)."""
    f = cfg.family
    if f == "dense":
        if cfg.alt_local_global:
            assert cfg.n_layers % 2 == 0
            return cfg.n_layers // 2, 2, 0
        return cfg.n_layers, 1, 0
    if f == "moe":
        return cfg.n_layers, 1, 0
    if f == "ssm":
        return cfg.n_layers, 1, 0
    if f == "hybrid":
        return cfg.n_layers // cfg.attn_every, cfg.attn_every, cfg.n_layers % cfg.attn_every
    if f == "vlm":
        assert cfg.n_layers % cfg.cross_every == 0
        return cfg.n_layers // cfg.cross_every, cfg.cross_every, 0
    if f == "encdec":
        return cfg.n_layers, 1, 0  # decoder blocks; encoder separate
    raise ValueError(cfg.family)


# ---------------------------------------------------------------------------
# Per-family single-superblock init
# ---------------------------------------------------------------------------

def _init_dense_layer(rng, cfg: ModelConfig):
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    p = {
        "attn_norm": init_rmsnorm(cfg.d_model),
        "attn": init_attention(k1, cfg),
        "mlp_norm": init_rmsnorm(cfg.d_model),
        "mlp": init_mlp(k2, cfg),
    }
    if cfg.post_norm:
        p["attn_post_norm"] = init_rmsnorm(cfg.d_model)
        p["mlp_post_norm"] = init_rmsnorm(cfg.d_model)
    return p


def _init_moe_layer(rng, cfg: ModelConfig):
    k1, k2 = jax.random.split(rng)
    return {
        "attn_norm": init_rmsnorm(cfg.d_model),
        "attn": init_attention(k1, cfg),
        "moe_norm": init_rmsnorm(cfg.d_model),
        "moe": init_moe(k2, cfg),
    }


def _init_ssm_layer(rng, cfg: ModelConfig):
    return {"norm": init_rmsnorm(cfg.d_model), "ssd": init_ssd(rng, cfg)}


def init_superblock(rng, cfg: ModelConfig):
    f = cfg.family
    if f == "dense":
        if cfg.alt_local_global:
            kl, kg = jax.random.split(rng)
            return {"local": _init_dense_layer(kl, cfg), "global": _init_dense_layer(kg, cfg)}
        return _init_dense_layer(rng, cfg)
    if f == "moe":
        return _init_moe_layer(rng, cfg)
    if f == "ssm":
        return _init_ssm_layer(rng, cfg)
    if f == "hybrid":
        ks = jax.random.split(rng, cfg.attn_every)
        return {"mamba": jax.vmap(lambda k: _init_ssm_layer(k, cfg))(ks)}
    if f == "vlm":
        n_self = cfg.cross_every - 1
        ks = jax.random.split(rng, n_self + 1)
        return {
            "self": jax.vmap(lambda k: _init_dense_layer(k, cfg))(ks[:n_self]),
            "cross": _init_dense_layer(ks[-1], cfg),
        }
    if f == "encdec":
        k1, k2, k3 = jax.random.split(rng, 3)
        return {
            "self_norm": init_rmsnorm(cfg.d_model),
            "self_attn": init_attention(k1, cfg),
            "cross_norm": init_rmsnorm(cfg.d_model),
            "cross_attn": init_attention(k2, cfg),
            "mlp_norm": init_rmsnorm(cfg.d_model),
            "mlp": init_mlp(k3, cfg),
        }
    raise ValueError(f)


def init_lm(rng, cfg: ModelConfig):
    """Init full model params.  Stacked superblocks under 'blocks'."""
    n_sb, _, tail = superblock_layout(cfg)
    ks = jax.random.split(rng, 8)
    Vp, D = cfg.padded_vocab, cfg.d_model
    params: Dict[str, Any] = {
        "tok_embed": _dense_init(ks[0], (Vp, D), D),
        "blocks": jax.vmap(lambda k: init_superblock(k, cfg))(jax.random.split(ks[1], n_sb)),
        "final_norm": init_rmsnorm(D),
        "lm_head": _dense_init(ks[2], (D, Vp), D),
        "value_head": _dense_init(ks[3], (D, 1), D),
    }
    if tail:  # zamba2 trailing mamba layers
        params["tail_blocks"] = jax.vmap(lambda k: _init_ssm_layer(k, cfg))(
            jax.random.split(ks[4], tail)
        )
    if cfg.family == "hybrid":
        k1, k2 = jax.random.split(ks[5])
        params["shared_attn"] = {
            "attn_norm": init_rmsnorm(D),
            "attn": init_attention(k1, cfg),
            "mlp_norm": init_rmsnorm(D),
            "mlp": init_mlp(k2, cfg),
        }
    if cfg.family == "encdec":
        params["encoder"] = {
            "blocks": jax.vmap(lambda k: _init_dense_layer(k, cfg))(
                jax.random.split(ks[6], cfg.n_enc_layers)
            ),
            "final_norm": init_rmsnorm(D),
        }
    return params


# ---------------------------------------------------------------------------
# Training-path superblock application
# ---------------------------------------------------------------------------

def _dense_layer_train(p, x, cfg: ModelConfig, *, window=None, positions=None,
                       x_kv=None, causal=True):
    h = rmsnorm(p["attn_norm"], x)
    a, _ = attention_train(p["attn"], h, cfg, positions=positions, causal=causal,
                           window=window, x_kv=x_kv)
    if cfg.post_norm:
        a = rmsnorm(p["attn_post_norm"], a)
    x = x + a
    x = constrain_res(x, cfg)
    h = rmsnorm(p["mlp_norm"], x)
    m = mlp(p["mlp"], h)
    if cfg.post_norm:
        m = rmsnorm(p["mlp_post_norm"], m)
    x = x + m
    return constrain_res(x, cfg)


def _moe_layer_train(p, x, cfg: ModelConfig, *, window=None, positions=None):
    h = rmsnorm(p["attn_norm"], x)
    a, _ = attention_train(p["attn"], h, cfg, positions=positions, window=window)
    x = constrain_res(x + a, cfg)
    h = rmsnorm(p["moe_norm"], x)
    m, aux = moe(p["moe"], h, cfg, groups=shd.n_batch_shards())
    return constrain_res(x + m, cfg), aux


def _ssm_layer_train(p, x, cfg: ModelConfig):
    h = rmsnorm(p["norm"], x)
    y, _ = ssd_block_train(p["ssd"], h, cfg)
    return constrain_res(x + y, cfg)


def apply_superblock_train(block_p, x, cfg: ModelConfig, *, shared=None,
                           img=None, enc_out=None, positions=None):
    """One superblock forward; returns (x, aux)."""
    f = cfg.family
    aux = jnp.zeros((), F32)
    if f == "dense":
        if cfg.alt_local_global:
            x = _dense_layer_train(block_p["local"], x, cfg, window=cfg.window,
                                   positions=positions)
            x = _dense_layer_train(block_p["global"], x, cfg, positions=positions)
        else:
            x = _dense_layer_train(block_p, x, cfg, window=cfg.window,
                                   positions=positions)
    elif f == "moe":
        x, aux = _moe_layer_train(block_p, x, cfg, window=cfg.window,
                                  positions=positions)
    elif f == "ssm":
        x = _ssm_layer_train(block_p, x, cfg)
    elif f == "hybrid":
        def body(xc, lp):
            return _ssm_layer_train(lp, xc, cfg), None
        x, _ = _scan(cfg, body, x, block_p["mamba"])
        x = _dense_layer_train(shared, x, cfg, positions=positions)
    elif f == "vlm":
        def body(xc, lp):
            return _dense_layer_train(lp, xc, cfg, positions=positions), None
        x, _ = _scan(cfg, body, x, block_p["self"])
        # cross-attention to image tokens (stub patch embeddings)
        x = _dense_layer_train(block_p["cross"], x, cfg, positions=positions,
                               x_kv=img, causal=False)
    elif f == "encdec":
        h = rmsnorm(block_p["self_norm"], x)
        a, _ = attention_train(block_p["self_attn"], h, cfg, positions=positions)
        x = constrain_res(x + a, cfg)
        h = rmsnorm(block_p["cross_norm"], x)
        a, _ = attention_train(block_p["cross_attn"], h, cfg, positions=positions,
                               x_kv=enc_out, causal=False)
        x = constrain_res(x + a, cfg)
        h = rmsnorm(block_p["mlp_norm"], x)
        x = constrain_res(x + mlp(block_p["mlp"], h), cfg)
    else:
        raise ValueError(f)
    return x, aux


def encoder_forward(params, frames, cfg: ModelConfig):
    """Whisper-style bidirectional encoder over precomputed frame embeddings
    (conv frontend stubbed per assignment).  frames: (B, S_enc, D)."""
    x = frames.astype(cdtype(cfg))
    x = constrain_res(x, cfg)
    pos = jnp.arange(frames.shape[1])

    def body(xc, lp):
        xc = _dense_layer_train(lp, xc, cfg, positions=pos, causal=False)
        return xc, None

    fn = jax.checkpoint(body) if cfg.remat else body
    x, _ = _scan(cfg, fn, x, params["encoder"]["blocks"])
    return rmsnorm(params["encoder"]["final_norm"], x)


def embed(params, tokens, cfg: ModelConfig):
    x = jnp.take(params["tok_embed"], tokens, axis=0).astype(cdtype(cfg))
    if cfg.family == "encdec" or cfg.softcap_logits is not None:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)  # gemma/whisper scale
    return x


def forward_train(params, tokens, cfg: ModelConfig, *, img=None, enc_frames=None):
    """tokens:(B,T) -> (hidden (B,T,D) bf16, aux scalar).  img: (B,I,D) stub
    patch embeddings (vlm); enc_frames: (B,S,D) stub frame embeddings (encdec)."""
    B, T = tokens.shape
    x = embed(params, tokens, cfg)
    x = constrain_res(x, cfg)
    # positions=None means "contiguous from 0" (attention_train fills in
    # arange(T)) — and marks the call site eligible for the flash-attention
    # kernel dispatch, which only handles the contiguous causal layout.
    positions = None
    enc_out = None
    if cfg.family == "encdec":
        enc_out = encoder_forward(params, enc_frames, cfg)
    if img is not None:
        img = img.astype(cdtype(cfg))
    shared = params.get("shared_attn")

    def body(carry, block_p):
        xc, aux = carry
        xc, a = apply_superblock_train(block_p, xc, cfg, shared=shared, img=img,
                                       enc_out=enc_out, positions=positions)
        return (xc, aux + a), None

    fn = jax.checkpoint(body) if cfg.remat else body
    (x, aux), _ = _scan(cfg, fn, (x, jnp.zeros((), F32)), params["blocks"])

    if "tail_blocks" in params:
        def tail_body(xc, lp):
            return _ssm_layer_train(lp, xc, cfg), None
        tfn = jax.checkpoint(tail_body) if cfg.remat else tail_body
        x, _ = _scan(cfg, tfn, x, params["tail_blocks"])

    x = rmsnorm(params["final_norm"], x)
    return x, aux


def lm_logits(params, hidden, cfg: ModelConfig):
    logits = jnp.einsum("...td,dv->...tv", hidden,
                        params["lm_head"].astype(hidden.dtype))
    if cfg.softcap_logits is not None:
        logits = jnp.tanh(logits / cfg.softcap_logits) * cfg.softcap_logits
    return shd.constrain(logits, P(shd.dp_axes(), None, shd.tp_axis()))


def value_out(params, hidden):
    return jnp.einsum("...td,dk->...tk", hidden.astype(F32),
                      params["value_head"])[..., 0]


# Leaves the forward reads only through ``.astype(cdtype(cfg))`` or
# ``.astype(x.dtype)`` on a compute-dtype activation: the matmul weights,
# the conv taps and the token table (a gather then a cast equals a cast then
# a gather).  Norm scales, ``A_log``, ``dt_bias`` and ``value_head`` are read
# in float32 and are not listed.
_COMPUTE_DTYPE_LEAVES = frozenset({
    "tok_embed", "lm_head",
    "wq", "wk", "wv", "wo",                              # attention
    "wi", "wg", "wd",                                    # SwiGLU MLP
    "router", "experts_wi", "experts_wg", "experts_wd",  # MoE
    "wz", "wx", "wB", "wC", "wdt", "conv_w", "out_proj",  # Mamba-2 SSD
})


def compute_weights(params, cfg: ModelConfig):
    """``params`` with every leaf that the forward casts at use held in the
    compute dtype already, so a program that serves many calls on frozen
    weights does not cast them on each call.  Every product gets the operand
    the cast at use gives it, so prefill, decode and logits are bitwise those
    of the float32 tree wherever the compiler rounds at that cast (always on
    the CPU; a TPU compile of a one-token program may leave a float32 weight
    unrounded).  Leaves read in float32 keep their dtype; idempotent."""
    dt = cdtype(cfg)

    def cast(path, leaf):
        name = getattr(path[-1], "key", None)
        return leaf.astype(dt) if name in _COMPUTE_DTYPE_LEAVES else leaf

    return jax.tree_util.tree_map_with_path(cast, params)


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------

def _kv_cache_spec(cfg: ModelConfig, B: int, S: int):
    """PartitionSpec for a stacked (n_sb, B, S, Hkv, dh) cache."""
    dp, tpax, tp = shd.dp_axes(), shd.tp_axis(), shd.tp_size()
    ndp = shd.n_batch_shards()
    b_ax = dp if (ndp > 1 and B % ndp == 0) else None
    if tp > 1 and cfg.n_kv_heads % tp == 0:
        h_ax, s_ax = tpax, None
    elif tp > 1 and S % tp == 0:
        h_ax, s_ax = None, tpax
    else:
        h_ax, s_ax = None, None
    if b_ax is None and ndp > 1 and S % (ndp * max(tp, 1)) == 0 and s_ax == tpax:
        s_ax = (dp if isinstance(dp, str) else tuple(dp)) + (tpax,) \
            if isinstance(dp, tuple) else (dp, tpax)
    elif b_ax is None and ndp > 1 and S % ndp == 0 and s_ax is None:
        s_ax = dp
    return P(None, b_ax, s_ax, h_ax, None)


def constrain_cache_kv(x, cfg: ModelConfig):
    if x.ndim != 5:
        return x
    return shd.constrain(x, _kv_cache_spec(cfg, x.shape[1], x.shape[2]))


def init_cache(cfg: ModelConfig, B: int, S: int, *, img_len: int = 0,
               enc_len: int = 0, dtype=None):
    """Allocate the serving cache for a batch of B sequences, max context S."""
    dt = dtype or cdtype(cfg)
    n_sb, _, tail = superblock_layout(cfg)
    H, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    f = cfg.family
    cache: Dict[str, Any] = {"lengths": jnp.zeros((B,), jnp.int32)}

    def kv(n, s):
        return (jnp.zeros((n, B, s, Hkv, dh), dt), jnp.zeros((n, B, s, Hkv, dh), dt))

    def ssm_states(n):
        Hs, Pd, G, N = cfg.ssm_n_heads, cfg.ssm_headdim, cfg.ssm_n_groups, cfg.d_state
        conv_dim = Hs * Pd + 2 * G * N
        return (
            jnp.zeros((n, B, cfg.conv_kernel - 1, conv_dim), dt),
            jnp.zeros((n, B, Hs, Pd, N), F32),
        )

    if f == "dense":
        if cfg.alt_local_global:
            Sl = min(cfg.window or S, S)
            cache["k_local"], cache["v_local"] = kv(n_sb, Sl)
            cache["k_global"], cache["v_global"] = kv(n_sb, S)
        else:
            Se = min(cfg.window or S, S)
            cache["k"], cache["v"] = kv(n_sb, Se)
    elif f == "moe":
        Se = min(cfg.window or S, S)
        cache["k"], cache["v"] = kv(n_sb, Se)
    elif f == "ssm":
        cache["conv"], cache["ssm"] = ssm_states(n_sb)
    elif f == "hybrid":
        cache["conv"], cache["ssm"] = ssm_states(n_sb * cfg.attn_every)
        cache["k"], cache["v"] = kv(n_sb, S)  # shared-attn sites
        if tail:
            cache["tail_conv"], cache["tail_ssm"] = ssm_states(tail)
    elif f == "vlm":
        cache["k"], cache["v"] = kv(n_sb * (cfg.cross_every - 1), S)
        cache["cross_k"], cache["cross_v"] = kv(n_sb, max(img_len, 1))
    elif f == "encdec":
        cache["k"], cache["v"] = kv(n_sb, S)
        cache["cross_k"], cache["cross_v"] = kv(n_sb, max(enc_len, 1))
    return cache


def cache_pspecs(cfg: ModelConfig, cache):
    """PartitionSpec tree for a cache (same rules as constrain_cache_kv)."""
    dp = shd.dp_axes()
    ndp = shd.n_batch_shards()

    def spec(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else ""
        if name == "lengths":
            return P(dp if ndp > 1 and leaf.shape[0] % ndp == 0 else None)
        if leaf.ndim == 5 and name in ("k", "v", "k_local", "v_local", "k_global",
                                       "v_global", "cross_k", "cross_v"):
            return _kv_cache_spec(cfg, leaf.shape[1], leaf.shape[2])
        # ssm conv/state: (n, B, ...) — batch over dp, heads over tp
        b_ax = dp if (ndp > 1 and leaf.shape[1] % ndp == 0) else None
        tp = shd.tp_size()
        if leaf.ndim == 5:  # ssm state (n,B,H,P,N)
            h_ax = shd.tp_axis() if tp > 1 and leaf.shape[2] % tp == 0 else None
            return P(None, b_ax, h_ax, None, None)
        if leaf.ndim == 4:  # conv state (n,B,K-1,C)
            c_ax = shd.tp_axis() if tp > 1 and leaf.shape[3] % tp == 0 else None
            return P(None, b_ax, None, c_ax)
        return P(*([None] * leaf.ndim))

    return jax.tree_util.tree_map_with_path(spec, cache)


# ---------------------------------------------------------------------------
# Decode path
# ---------------------------------------------------------------------------

def _dense_layer_decode(p, x, ck, cv, lengths, cfg, *, window=None):
    h = rmsnorm(p["attn_norm"], x)
    a, nk, nv = attention_decode(p["attn"], h, ck, cv, lengths, cfg, window=window)
    if cfg.post_norm:
        a = rmsnorm(p["attn_post_norm"], a)
    x = x + a
    h = rmsnorm(p["mlp_norm"], x)
    m = mlp(p["mlp"], h)
    if cfg.post_norm:
        m = rmsnorm(p["mlp_post_norm"], m)
    return x + m, nk, nv


def _moe_layer_decode(p, x, ck, cv, lengths, cfg, *, window=None):
    h = rmsnorm(p["attn_norm"], x)
    a, nk, nv = attention_decode(p["attn"], h, ck, cv, lengths, cfg, window=window)
    x = x + a
    h = rmsnorm(p["moe_norm"], x)
    # exact (no-drop) dispatch by default; capacity-bounded when the perf
    # knob is set (cuts dense-dispatch compute by ~E/(K*cf), rare drops)
    if cfg.decode_capacity_factor > 0:
        m, _ = moe(p["moe"], h, cfg, groups=1,
                   capacity_factor=cfg.decode_capacity_factor)
    else:
        m, _ = moe(p["moe"], h, cfg, groups=1, no_drop=True)
    return x + m, nk, nv


def decode_step(params, cache, tokens, cfg: ModelConfig, *, active=None):
    """One decode token for the whole batch.  tokens:(B,) int32.
    Returns (hidden (B,1,D), new_cache).

    ``active`` ((B,) bool, optional) is the continuous-batching slot mask:
    retired slots keep stepping (the program stays shape-stable, so zero
    recompilation) but their ``lengths`` are NOT bumped — their outputs are
    dead and their cache slot is fully overwritten on the next
    ``write_prefill_at`` (serving/slots.py) before reuse."""
    B = tokens.shape[0]
    lengths = cache["lengths"]
    x = embed(params, tokens[:, None], cfg)
    f = cfg.family
    new_cache = dict(cache)

    if f in ("dense", "moe") and not cfg.alt_local_global:
        layer_fn = _moe_layer_decode if f == "moe" else _dense_layer_decode

        def body(xc, xs):
            lp, ck, cv = xs
            xc, nk, nv = layer_fn(lp, xc, ck, cv, lengths, cfg, window=cfg.window)
            return xc, (nk, nv)

        x, (nk, nv) = _scan(cfg, body, x, (params["blocks"], cache["k"], cache["v"]))
        new_cache["k"], new_cache["v"] = constrain_cache_kv(nk, cfg), constrain_cache_kv(nv, cfg)

    elif f == "dense" and cfg.alt_local_global:
        def body(xc, xs):
            lp, ckl, cvl, ckg, cvg = xs
            xc, nkl, nvl = _dense_layer_decode(lp["local"], xc, ckl, cvl, lengths,
                                               cfg, window=cfg.window)
            xc, nkg, nvg = _dense_layer_decode(lp["global"], xc, ckg, cvg, lengths, cfg)
            return xc, (nkl, nvl, nkg, nvg)

        x, (nkl, nvl, nkg, nvg) = _scan(cfg, 
            body, x,
            (params["blocks"], cache["k_local"], cache["v_local"],
             cache["k_global"], cache["v_global"]))
        new_cache["k_local"], new_cache["v_local"] = nkl, nvl
        new_cache["k_global"], new_cache["v_global"] = constrain_cache_kv(nkg, cfg), constrain_cache_kv(nvg, cfg)

    elif f == "ssm":
        def body(xc, xs):
            lp, cs, ss = xs
            h = rmsnorm(lp["norm"], xc)
            y, (ncs, nss) = ssd_block_decode(lp["ssd"], h, cs, ss, cfg)
            return xc + y, (ncs, nss)

        x, (ncs, nss) = _scan(cfg, body, x, (params["blocks"], cache["conv"], cache["ssm"]))
        new_cache["conv"], new_cache["ssm"] = ncs, nss

    elif f == "hybrid":
        ae = cfg.attn_every
        n_sb = superblock_layout(cfg)[0]
        conv = cache["conv"].reshape((n_sb, ae) + cache["conv"].shape[1:])
        ssm = cache["ssm"].reshape((n_sb, ae) + cache["ssm"].shape[1:])
        shared = params["shared_attn"]

        def body(xc, xs):
            bp, cs_g, ss_g, ck, cv = xs

            def inner(xi, ys):
                lp, cs, ss = ys
                h = rmsnorm(lp["norm"], xi)
                y, (ncs, nss) = ssd_block_decode(lp["ssd"], h, cs, ss, cfg)
                return xi + y, (ncs, nss)

            xc, (ncs_g, nss_g) = _scan(cfg, inner, xc, (bp["mamba"], cs_g, ss_g))
            xc, nk, nv = _dense_layer_decode(shared, xc, ck, cv, lengths, cfg)
            return xc, (ncs_g, nss_g, nk, nv)

        x, (nconv, nssm, nk, nv) = _scan(cfg, 
            body, x, (params["blocks"], conv, ssm, cache["k"], cache["v"]))
        new_cache["conv"] = nconv.reshape(cache["conv"].shape)
        new_cache["ssm"] = nssm.reshape(cache["ssm"].shape)
        new_cache["k"], new_cache["v"] = constrain_cache_kv(nk, cfg), constrain_cache_kv(nv, cfg)
        if "tail_conv" in cache:
            def tail(xc, xs):
                lp, cs, ss = xs
                h = rmsnorm(lp["norm"], xc)
                y, (ncs, nss) = ssd_block_decode(lp["ssd"], h, cs, ss, cfg)
                return xc + y, (ncs, nss)
            x, (ntc, nts) = _scan(cfg, 
                tail, x, (params["tail_blocks"], cache["tail_conv"], cache["tail_ssm"]))
            new_cache["tail_conv"], new_cache["tail_ssm"] = ntc, nts

    elif f == "vlm":
        ns = cfg.cross_every - 1
        n_sb = superblock_layout(cfg)[0]
        ks = cache["k"].reshape((n_sb, ns) + cache["k"].shape[1:])
        vs = cache["v"].reshape((n_sb, ns) + cache["v"].shape[1:])

        def body(xc, xs):
            bp, k_g, v_g, cxk, cxv = xs

            def inner(xi, ys):
                lp, ck, cv = ys
                xi, nk, nv = _dense_layer_decode(lp, xi, ck, cv, lengths, cfg)
                return xi, (nk, nv)

            xc, (nk_g, nv_g) = _scan(cfg, inner, xc, (bp["self"], k_g, v_g))
            # cross layer: frozen image KV
            cp = bp["cross"]
            h = rmsnorm(cp["attn_norm"], xc)
            a = cross_attention_decode(cp["attn"], h, cxk, cxv, cfg)
            xc = xc + a
            h = rmsnorm(cp["mlp_norm"], xc)
            xc = xc + mlp(cp["mlp"], h)
            return xc, (nk_g, nv_g)

        x, (nk, nv) = _scan(cfg, 
            body, x, (params["blocks"], ks, vs, cache["cross_k"], cache["cross_v"]))
        new_cache["k"] = constrain_cache_kv(nk.reshape(cache["k"].shape), cfg)
        new_cache["v"] = constrain_cache_kv(nv.reshape(cache["v"].shape), cfg)

    elif f == "encdec":
        def body(xc, xs):
            bp, ck, cv, cxk, cxv = xs
            h = rmsnorm(bp["self_norm"], xc)
            a, nk, nv = attention_decode(bp["self_attn"], h, ck, cv, lengths, cfg)
            xc = xc + a
            h = rmsnorm(bp["cross_norm"], xc)
            xc = xc + cross_attention_decode(bp["cross_attn"], h, cxk, cxv, cfg)
            h = rmsnorm(bp["mlp_norm"], xc)
            xc = xc + mlp(bp["mlp"], h)
            return xc, (nk, nv)

        x, (nk, nv) = _scan(cfg, 
            body, x, (params["blocks"], cache["k"], cache["v"],
                      cache["cross_k"], cache["cross_v"]))
        new_cache["k"], new_cache["v"] = constrain_cache_kv(nk, cfg), constrain_cache_kv(nv, cfg)
    else:
        raise ValueError(f)

    bump = jnp.ones((B,), jnp.int32) if active is None else active.astype(jnp.int32)
    new_cache["lengths"] = lengths + bump
    x = rmsnorm(params["final_norm"], x)
    return x, new_cache


# ---------------------------------------------------------------------------
# Prefill: full-sequence forward that also fills the cache
# ---------------------------------------------------------------------------

def _fill_kv(cache_k, cache_v, k, v, window):
    """Write training-path K/V (B,T,Hkv,dh) into a fresh cache (B,S,Hkv,dh)."""
    S = cache_k.shape[1]
    T = k.shape[1]
    if window is not None and S == window and T > S:
        k, v = k[:, -S:], v[:, -S:]
        # rolling buffer: slot i holds absolute position p where p % S == i
        roll = (T - S) % S
        k, v = jnp.roll(k, roll, axis=1), jnp.roll(v, roll, axis=1)
        return cache_k.at[:].set(k.astype(cache_k.dtype)), cache_v.at[:].set(v.astype(cache_v.dtype))
    Tw = min(T, S)
    nk = jax.lax.dynamic_update_slice(cache_k, k[:, :Tw].astype(cache_k.dtype), (0, 0, 0, 0))
    nv = jax.lax.dynamic_update_slice(cache_v, v[:, :Tw].astype(cache_v.dtype), (0, 0, 0, 0))
    return nk, nv


def prefill(params, tokens, cfg: ModelConfig, cache, *, img=None, enc_frames=None):
    """Run the full-sequence forward, returning (last_hidden (B,1,D), cache).

    The cache must be freshly initialized (lengths == 0).  Implemented as the
    train forward with K/V capture per attention layer — one compiled program,
    chunked attention, last-token logits only.
    """
    B, T = tokens.shape
    x = embed(params, tokens, cfg)
    x = constrain_res(x, cfg)
    positions = None  # contiguous-from-0: kernel-dispatch eligible (see forward_train)
    f = cfg.family
    new_cache = dict(cache)
    enc_out = None
    if f == "encdec":
        enc_out = encoder_forward(params, enc_frames, cfg)
    if img is not None:
        img = img.astype(cdtype(cfg))

    def attn_capture(p, xc, *, window=None, x_kv=None, causal=True):
        h = rmsnorm(p["attn_norm"], xc)
        a, (k, v) = attention_train(p["attn"], h, cfg, positions=positions,
                                    causal=causal, window=window, x_kv=x_kv)
        if cfg.post_norm:
            a = rmsnorm(p["attn_post_norm"], a)
        xc = constrain_res(xc + a, cfg)
        if f == "moe":
            h = rmsnorm(p["moe_norm"], xc)
            m, _ = moe(p["moe"], h, cfg, groups=shd.n_batch_shards())
        else:
            h = rmsnorm(p["mlp_norm"], xc)
            m = mlp(p["mlp"], h)
            if cfg.post_norm:
                m = rmsnorm(p["mlp_post_norm"], m)
        return constrain_res(xc + m, cfg), k, v

    if f in ("dense", "moe") and not cfg.alt_local_global:
        def body(xc, xs):
            lp, ck, cv = xs
            xc, k, v = attn_capture(lp, xc, window=cfg.window)
            nk, nv = _fill_kv(ck, cv, k, v, cfg.window)
            return xc, (nk, nv)
        fn = jax.checkpoint(body) if cfg.remat else body
        x, (nk, nv) = _scan(cfg, fn, x, (params["blocks"], cache["k"], cache["v"]))
        new_cache["k"], new_cache["v"] = constrain_cache_kv(nk, cfg), constrain_cache_kv(nv, cfg)

    elif f == "dense" and cfg.alt_local_global:
        def body(xc, xs):
            lp, ckl, cvl, ckg, cvg = xs
            xc, kl, vl = attn_capture(lp["local"], xc, window=cfg.window)
            nkl, nvl = _fill_kv(ckl, cvl, kl, vl, cfg.window)
            xc, kg, vg = attn_capture(lp["global"], xc)
            nkg, nvg = _fill_kv(ckg, cvg, kg, vg, None)
            return xc, (nkl, nvl, nkg, nvg)
        fn = jax.checkpoint(body) if cfg.remat else body
        x, (nkl, nvl, nkg, nvg) = _scan(cfg, 
            fn, x, (params["blocks"], cache["k_local"], cache["v_local"],
                    cache["k_global"], cache["v_global"]))
        new_cache["k_local"], new_cache["v_local"] = nkl, nvl
        new_cache["k_global"], new_cache["v_global"] = constrain_cache_kv(nkg, cfg), constrain_cache_kv(nvg, cfg)

    elif f == "ssm":
        def body(xc, xs):
            lp, cs, ss = xs
            h = rmsnorm(lp["norm"], xc)
            y, (ncs, nss) = ssd_block_train(lp["ssd"], h, cfg, conv_state=cs, ssm_state=ss)
            return xc + y, (ncs, nss)
        fn = jax.checkpoint(body) if cfg.remat else body
        x, (ncs, nss) = _scan(cfg, fn, x, (params["blocks"], cache["conv"], cache["ssm"]))
        new_cache["conv"], new_cache["ssm"] = ncs, nss

    elif f == "hybrid":
        ae = cfg.attn_every
        n_sb = superblock_layout(cfg)[0]
        conv = cache["conv"].reshape((n_sb, ae) + cache["conv"].shape[1:])
        ssm = cache["ssm"].reshape((n_sb, ae) + cache["ssm"].shape[1:])
        shared = params["shared_attn"]

        def body(xc, xs):
            bp, cs_g, ss_g, ck, cv = xs

            def inner(xi, ys):
                lp, cs, ss = ys
                h = rmsnorm(lp["norm"], xi)
                y, (ncs, nss) = ssd_block_train(lp["ssd"], h, cfg, conv_state=cs, ssm_state=ss)
                return xi + y, (ncs, nss)

            xc, (ncs_g, nss_g) = _scan(cfg, inner, xc, (bp["mamba"], cs_g, ss_g))
            xc, k, v = attn_capture(shared, xc)
            nk, nv = _fill_kv(ck, cv, k, v, None)
            return xc, (ncs_g, nss_g, nk, nv)

        fn = jax.checkpoint(body) if cfg.remat else body
        x, (nconv, nssm, nk, nv) = _scan(cfg, 
            fn, x, (params["blocks"], conv, ssm, cache["k"], cache["v"]))
        new_cache["conv"] = nconv.reshape(cache["conv"].shape)
        new_cache["ssm"] = nssm.reshape(cache["ssm"].shape)
        new_cache["k"], new_cache["v"] = constrain_cache_kv(nk, cfg), constrain_cache_kv(nv, cfg)
        if "tail_conv" in cache:
            def tail(xc, xs):
                lp, cs, ss = xs
                h = rmsnorm(lp["norm"], xc)
                y, (ncs, nss) = ssd_block_train(lp["ssd"], h, cfg, conv_state=cs, ssm_state=ss)
                return xc + y, (ncs, nss)
            x, (ntc, nts) = _scan(cfg, 
                tail, x, (params["tail_blocks"], cache["tail_conv"], cache["tail_ssm"]))
            new_cache["tail_conv"], new_cache["tail_ssm"] = ntc, nts

    elif f == "vlm":
        ns = cfg.cross_every - 1
        n_sb = superblock_layout(cfg)[0]
        ks = cache["k"].reshape((n_sb, ns) + cache["k"].shape[1:])
        vs = cache["v"].reshape((n_sb, ns) + cache["v"].shape[1:])
        dt = cdtype(cfg)

        def body(xc, xs):
            bp, k_g, v_g, cxk, cxv = xs

            def inner(xi, ys):
                lp, ck, cv = ys
                xi, k, v = attn_capture(lp, xi)
                nk, nv = _fill_kv(ck, cv, k, v, None)
                return xi, (nk, nv)

            xc, (nk_g, nv_g) = _scan(cfg, inner, xc, (bp["self"], k_g, v_g))
            cp = bp["cross"]
            h = rmsnorm(cp["attn_norm"], xc)
            a, (ik, iv) = attention_train(cp["attn"], h, cfg, positions=positions,
                                          causal=False, x_kv=img)
            xc = constrain_res(xc + a, cfg)
            h = rmsnorm(cp["mlp_norm"], xc)
            xc = constrain_res(xc + mlp(cp["mlp"], h), cfg)
            return xc, (nk_g, nv_g, ik.astype(dt), iv.astype(dt))

        fn = jax.checkpoint(body) if cfg.remat else body
        x, (nk, nv, cxk, cxv) = _scan(cfg, fn, x, (params["blocks"], ks, vs,
                                                     cache["cross_k"], cache["cross_v"]))
        new_cache["k"] = constrain_cache_kv(nk.reshape(cache["k"].shape), cfg)
        new_cache["v"] = constrain_cache_kv(nv.reshape(cache["v"].shape), cfg)
        new_cache["cross_k"], new_cache["cross_v"] = cxk, cxv

    elif f == "encdec":
        dt = cdtype(cfg)

        def body(xc, xs):
            bp, ck, cv = xs
            h = rmsnorm(bp["self_norm"], xc)
            a, (k, v) = attention_train(bp["self_attn"], h, cfg, positions=positions)
            xc = constrain_res(xc + a, cfg)
            nk, nv = _fill_kv(ck, cv, k, v, None)
            h = rmsnorm(bp["cross_norm"], xc)
            a, (xk, xv) = attention_train(bp["cross_attn"], h, cfg, positions=positions,
                                          x_kv=enc_out, causal=False)
            xc = constrain_res(xc + a, cfg)
            h = rmsnorm(bp["mlp_norm"], xc)
            xc = constrain_res(xc + mlp(bp["mlp"], h), cfg)
            return xc, (nk, nv, xk.astype(dt), xv.astype(dt))

        fn = jax.checkpoint(body) if cfg.remat else body
        x, (nk, nv, cxk, cxv) = _scan(cfg, 
            fn, x, (params["blocks"], cache["k"], cache["v"]))
        new_cache["k"], new_cache["v"] = constrain_cache_kv(nk, cfg), constrain_cache_kv(nv, cfg)
        new_cache["cross_k"], new_cache["cross_v"] = cxk, cxv
    else:
        raise ValueError(f)

    new_cache["lengths"] = cache["lengths"] + T
    x_last = x[:, -1:, :]
    x_last = rmsnorm(params["final_norm"], x_last)
    return x_last, new_cache
