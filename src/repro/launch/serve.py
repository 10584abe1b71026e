"""Serving drivers: fixed-batch prefill+decode rounds, and the continuous-
batching (in-flight) service loop — the paper's batched action selection as
a standalone service, TorchBeast-style dynamic batching included.

Two modes:

- default: the fixed-batch smoke driver.  Prefill and decode compile as
  SEPARATE programs so the service reports per-phase telemetry — prefill
  tokens/sec, decode tokens/sec, per-decode-step latency — through the same
  ``MetricsRegistry`` schema that ``benchmarks/bench_serving.py`` consumes:
  see :func:`timed_generate`.
- ``--continuous``: replay a Poisson arrival trace of mixed-length requests
  through ``serving/engine.py`` — slot-based KV-cache scheduling, bucketed
  single-prompt prefill into freed slots, zero steady-state recompilation —
  and report p50/p99 request latency, time-to-first-token, and decode
  tokens/sec through the same registry schema (``serve.jsonl``).

``--log-dir`` lands rows in console + JSONL; ``--profile[=DIR]`` captures a
perfetto-loadable trace with the serving spans annotated.

  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-1.3b --smoke \
      --batch 8 --prompt-len 64 --gen 32
  PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b --smoke \
      --continuous --requests 16 --rate 16 --gen 32
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp

from ..configs import get_config, get_smoke_config
from ..models import backbones as bb
from ..serving import ContinuousBatchEngine, DEFAULT_BUCKETS, poisson_trace
from ..telemetry import trace
from ..telemetry.metrics import MetricsRegistry
from ..utils.compile_cache import enable_compile_cache
from ..kernels import registry as kernel_registry

F32 = jnp.float32


def make_phases(cfg, batch: int, prompt_len: int, gen: int,
                temperature: float = 0.0):
    """Jitted (prefill, decode) pair.

    prefill(params, prompts) -> (last_logits, cache)
    decode(params, logits, cache, rng) -> (batch, gen) tokens

    Two programs instead of one so the host can time (and profile-annotate)
    each serving phase; the decode scan is unchanged, so per-step cost is
    identical to the fully-fused generate.  Prefill is deterministic and
    takes no key; sampling randomness belongs to decode alone.
    """
    S = prompt_len + gen + 1

    @jax.jit
    def prefill(params, prompts):
        kw = {}
        if cfg.family == "vlm":
            kw["img"] = jnp.zeros((batch, cfg.n_img_tokens, cfg.d_model),
                                  jnp.bfloat16)
        if cfg.family == "encdec":
            kw["enc_frames"] = jnp.zeros((batch, cfg.enc_len, cfg.d_model),
                                         jnp.bfloat16)
        cache = bb.init_cache(cfg, batch, S, img_len=cfg.n_img_tokens,
                              enc_len=cfg.enc_len)
        hidden, cache = bb.prefill(params, prompts, cfg, cache, **kw)
        logits = bb.lm_logits(params, hidden, cfg)[:, -1].astype(F32)
        return logits, cache

    @jax.jit
    def decode(params, logits, cache, rng):
        def step(carry, k):
            logits, cache = carry
            if temperature > 0:
                tok = jax.random.categorical(k, logits / temperature)
            else:
                tok = jnp.argmax(logits, axis=-1)
            hidden, cache = bb.decode_step(params, cache, tok, cfg)
            nxt = bb.lm_logits(params, hidden, cfg)[:, 0].astype(F32)
            return (nxt, cache), tok

        _, toks = jax.lax.scan(step, (logits, cache),
                               jax.random.split(rng, gen))
        return jnp.swapaxes(toks, 0, 1)  # (batch, gen)

    return prefill, decode


def make_generate(cfg, batch: int, prompt_len: int, gen: int,
                  temperature: float = 0.0):
    """Composed prefill+decode (the original single-call generate API).
    The caller's key goes to the decode phase only — prefill is
    deterministic (the seed driver passed the SAME key to both phases and
    prefill silently ignored it)."""
    prefill, decode = make_phases(cfg, batch, prompt_len, gen, temperature)

    def generate(params, prompts, rng):
        logits, cache = prefill(params, prompts)
        return decode(params, logits, cache, rng)

    return generate


def timed_generate(prefill, decode, params, prompts, rng, *,
                   batch: int, prompt_len: int, gen: int):
    """One serving round with per-phase timing.

    Returns ``(tokens, metrics)`` where metrics is THE serving telemetry
    schema — shared by the launch driver, bench_serving, and anything else
    that reports decode throughput:

    prefill_tok_per_sec, decode_tok_per_sec, decode_step_ms (per-step decode
    latency across the batch), latency_s (whole round), total_tok_per_sec.

    ``rng`` is consumed by the decode phase only (prefill is deterministic).
    """
    tracer = trace.get_tracer()
    t0 = time.perf_counter()
    with tracer.span("serve.prefill", tokens=batch * prompt_len):
        logits, cache = prefill(params, prompts)
        jax.block_until_ready(logits)
    t1 = time.perf_counter()
    with tracer.span("serve.decode", tokens=batch * gen):
        toks = decode(params, logits, cache, rng)
        jax.block_until_ready(toks)
    t2 = time.perf_counter()
    prefill_s, decode_s = t1 - t0, t2 - t1
    metrics = {
        "prefill_tok_per_sec": batch * prompt_len / max(prefill_s, 1e-9),
        "decode_tok_per_sec": batch * gen / max(decode_s, 1e-9),
        "decode_step_ms": decode_s / max(gen, 1) * 1e3,
        "latency_s": t2 - t0,
        "total_tok_per_sec": batch * (prompt_len + gen) / max(t2 - t0, 1e-9),
    }
    return toks, metrics


def _run_fixed(args, cfg, params, tracer, registry):
    """The fixed-batch rounds driver (original smoke path)."""
    rng = jax.random.PRNGKey(args.seed)
    prefill, decode = make_phases(cfg, args.batch, args.prompt_len, args.gen,
                                  args.temperature)
    tracer.watch_jit("serve.prefill", prefill)
    tracer.watch_jit("serve.decode", decode)

    toks = None
    for r in range(args.rounds):
        rng, k_prompt, k_decode = jax.random.split(rng, 3)
        prompts = jax.random.randint(k_prompt, (args.batch, args.prompt_len),
                                     0, cfg.vocab)
        toks, metrics = timed_generate(prefill, decode, params, prompts,
                                       k_decode, batch=args.batch,
                                       prompt_len=args.prompt_len,
                                       gen=args.gen)
        registry.record(r, {"arch": args.arch, "batch": args.batch,
                            "prompt_len": args.prompt_len, "gen": args.gen,
                            **metrics})
        tracer.poll_recompiles()
        tracer.memory_snapshot(f"round_{r}")
    if toks is not None:  # --rounds 0 runs nothing — nothing to echo
        print(f"first seq: {toks[0][:8].tolist()}")
    return toks


def _run_continuous(args, cfg, params, tracer, registry):
    """Continuous-batching service: replay a Poisson trace, report THE
    serving schema plus p50/p99 latency and TTFT."""
    n_slots = args.slots or args.batch
    buckets = [b for b in DEFAULT_BUCKETS if b <= args.prompt_len] or \
        [args.prompt_len]
    prompt_min = max(args.prompt_min, min(buckets))
    max_context = args.prompt_len + args.gen + 1
    engine = ContinuousBatchEngine(
        cfg, params, n_slots=n_slots, max_context=max_context,
        buckets=buckets, decode_block=args.decode_block,
        temperature=args.temperature, eos_id=args.eos_id,
        max_queue=args.max_queue, seed=args.seed)
    engine.watch(tracer)
    with tracer.span("serve.warmup"):
        engine.warmup()
    reqs = poisson_trace(args.seed, args.requests, args.rate,
                         prompt_len_range=(prompt_min, args.prompt_len),
                         max_tokens_range=(args.gen_min, args.gen),
                         vocab=cfg.vocab)
    with tracer.span("serve.continuous", requests=len(reqs)):
        summary = engine.run(reqs, mode="continuous", tracer=tracer)
    registry.record(0, {"arch": args.arch, "slots": n_slots,
                        "decode_block": args.decode_block, **summary})
    tracer.memory_snapshot("continuous_done")
    return summary


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-1.3b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-dir", default=None)
    # continuous-batching service flags
    ap.add_argument("--continuous", action="store_true",
                    help="replay a Poisson arrival trace through the "
                         "in-flight batching engine (serving/engine.py) "
                         "instead of fixed-batch rounds")
    ap.add_argument("--requests", type=int, default=16,
                    help="[continuous] number of requests in the trace")
    ap.add_argument("--rate", type=float, default=16.0,
                    help="[continuous] Poisson arrival rate, requests/sec")
    ap.add_argument("--slots", type=int, default=None,
                    help="[continuous] batch slots (default: --batch)")
    ap.add_argument("--decode-block", type=int, default=4,
                    help="[continuous] decode steps fused per dispatch; "
                         "slots swap at block boundaries")
    ap.add_argument("--prompt-min", type=int, default=8,
                    help="[continuous] minimum prompt length in the trace")
    ap.add_argument("--gen-min", type=int, default=4,
                    help="[continuous] minimum generation budget")
    ap.add_argument("--eos-id", type=int, default=None,
                    help="[continuous] retire a slot on this token id")
    ap.add_argument("--max-queue", type=int, default=256,
                    help="[continuous] admission cap: waiting requests "
                         "beyond this are rejected")
    ap.add_argument("--kernels", default=None,
                    help="kernel backend spec (REPRO_KERNELS syntax: 'ref', "
                         "'interpret', 'attention=pallas', ...); installed "
                         "before the generate program is traced")
    ap.add_argument("--profile", nargs="?", const="", default=None,
                    metavar="DIR",
                    help="capture a jax.profiler trace into DIR (default "
                         "<log-dir>/profile)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    tracer = trace.configure(os.path.join(args.log_dir, "trace.jsonl")
                             if args.log_dir else None)
    registry = MetricsRegistry(args.log_dir, sinks=("console", "jsonl"),
                               jsonl_filename="serve.jsonl")
    profile_dir = None
    if args.profile is not None:
        profile_dir = args.profile or os.path.join(args.log_dir or ".",
                                                   "profile")
        jax.profiler.start_trace(profile_dir)

    if args.kernels:
        kernel_registry.set_env(args.kernels)
    print(f"kernel backends: {kernel_registry.describe()}")
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    k_init = jax.random.PRNGKey(args.seed)
    params = bb.init_lm(jax.random.split(k_init)[0], cfg)

    if args.continuous:
        out = _run_continuous(args, cfg, params, tracer, registry)
    else:
        out = _run_fixed(args, cfg, params, tracer, registry)

    if profile_dir is not None:
        jax.profiler.stop_trace()
        print(f"profiler trace written to {profile_dir}")
    registry.close()
    return out


if __name__ == "__main__":
    main()
