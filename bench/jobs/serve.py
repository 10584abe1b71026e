"""Serving job: open-loop request traffic through the program's
continuous-batching engine (``serving/engine.py``, ``ContinuousBatchEngine
.run``), greedy decoding.

Set-up makes the weights from the seed, builds the engine and warms up the
programs this mix uses: the prefill of every bucket its prompt lengths
fall into, the one-token tail advance, the slot write and the decode
block.  The window replays the mix's requests at their due times; requests
still running when the window closes drain until ``drain_s`` after it.

The throughput counts the tokens of every decode block that the host has
read back by the window's close, over the time from the window's start to
the last such readback: what the engine served while the window was open,
and nothing of the drain after it.

Each request's times count from when it was due: TTFT is its first token's
time minus its due time, TPOT the gap between its first and last token
over the tokens between.  A request that is rejected, or not finished by
the drain deadline, has failed; it counts as missing every limit, with the
time from its due time to the deadline standing for its wait.

Traffic parameters (``bench/traffic/<name>.json``): n_slots, decode_block,
max_queue, drain_s, the arrivals and length distributions of
``bench/traffic.py``, check_requests and the limit of the check.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import common, traffic, weights


def buckets_used(default, pmin: int, pmax: int):
    """The program's prefill buckets that prompts of pmin..pmax tokens
    are served through (the largest bucket <= the prompt length)."""
    return sorted({max(b for b in default if b <= n)
                   for n in range(pmin, pmax + 1)})


def build_engine(spec, params, seed):
    from repro.models.config import ModelConfig
    from repro.serving.engine import ContinuousBatchEngine
    from repro.serving.slots import DEFAULT_BUCKETS

    tr = spec["traffic"]
    cfg = ModelConfig(**spec["config"]["model"])
    p, o = tr["prompt"], tr["output"]
    return ContinuousBatchEngine(
        cfg, params, n_slots=tr["n_slots"],
        max_context=p["max"] + o["max"] + 1,
        buckets=buckets_used(DEFAULT_BUCKETS, p["min"], p["max"]),
        decode_block=tr["decode_block"], temperature=0.0,
        max_queue=tr["max_queue"], seed=seed % 2 ** 31)


def latencies(reqs, seconds: float, drain_s: float):
    """(ttft_s, tpot_s, failed) over every request due in the window."""
    deadline = seconds + drain_s
    ttft, tpot, failed = [], [], 0
    for r in reqs:
        ok = r.t_finished is not None and r.t_finished <= deadline
        if not ok:
            failed += 1
            wait = max(deadline - r.arrival_s, 0.0)
            ttft.append(wait)
            tpot.append(wait)
            continue
        ttft.append(r.t_first_token - r.arrival_s)
        if r.n_generated > 1:
            tpot.append((r.t_finished - r.t_first_token)
                        / (r.n_generated - 1))
    return ttft, tpot, failed


def run(run: common.Run, spec: dict, *, fault=None) -> None:
    """One run of the cell.  ``fault`` (tests only): "altered_token"
    changes one token of each decode block where it is produced."""
    import jax
    from repro.serving.workload import Request
    from repro.telemetry import trace

    tr, model = spec["traffic"], spec["config"]["model"]
    vp = spec["config"]["padded_vocab"]
    tracer = trace.configure(None)
    params = weights.make_mamba2(run.seed, model, vp)
    engine = build_engine(spec, params, run.seed)
    if fault == "altered_token":
        _alter_tokens(engine)
    blocks = _count_blocks(engine)
    engine.watch(tracer)
    engine.warmup()
    # engine.run splits its key before every decode block, a program the
    # engine's own warm-up leaves out: compile it here, not in the window
    _, _ = jax.random.split(jax.random.PRNGKey(engine.seed))
    blocks.clear()
    specs = traffic.requests(run.seed, run.seconds, tr, model["vocab"])
    reqs = [Request(rid=i, prompt=s.prompt, max_tokens=s.max_tokens,
                    arrival_s=s.due_s) for i, s in enumerate(specs)]

    run.setup_done()
    with run.window(timer=True):
        t0 = time.perf_counter()
        summary = engine.run(reqs, tracer=tracer)
    run.read_memory()

    ttft, tpot, failed = latencies(reqs, run.seconds, tr["drain_s"])
    in_window = [(t, n) for t, n in blocks if t - t0 <= run.seconds]
    admitted = [r for r in reqs if r.t_admitted is not None]
    prefill_s = (sum(r.prompt_len for r in admitted)
                 / summary["prefill_tok_per_sec"]) if admitted else 0.0
    run.record.update(
        attempted=len(reqs), failed=failed, ttft_s=ttft, tpot_s=tpot,
        window_tokens=sum(n for _, n in in_window),
        window_s=(in_window[-1][0] - t0) if in_window else None,
        admitted=len(admitted), prefill_s=prefill_s,
        decode_step_ms=summary["decode_step_ms"],
        decode_block=tr["decode_block"],
        generated_tokens=summary["generated_tokens"],
        recompiles=summary["recompile_events"])

    sample = check_sample(reqs, tr, run.seed)
    del engine, params, summary
    gc.collect()
    run.checks.extend(compare(served_gap(spec, run.seed, sample),
                              tr["limits"]))


def compare(gap: float, limits: dict):
    """The number that decides ``correct``, beside its limit."""
    return [common.Check("served_token_gap", gap, limits["served_token_gap"])]


def served_gap(spec, seed, sample, *, control=False) -> float:
    """The widest gap of the sample's served tokens below the reference's
    best logit (``control``: of the tokens the float8 forward ranks
    first), with the weights made again from the seed."""
    import jax
    import jax.numpy as jnp

    from bench.reference import mamba2 as reference

    seqs, first, count = sample
    params = weights.make_mamba2(seed, spec["config"]["model"],
                                 spec["config"]["padded_vocab"])
    gaps = jax.jit(reference.served_token_gaps, static_argnames="control")(
        params, jnp.asarray(seqs), jnp.asarray(first), jnp.asarray(count),
        control=control)
    return float(jnp.max(gaps))


def calibrate(spec: dict, seed: int, seconds: float, *, fault=None) -> dict:
    """The number compared, for the program and for the control, on one
    seed, after a window of ``seconds`` at the cell's load, and the
    control's verdict by :func:`compare` against the cell's limit; with
    ``fault``, for the program so broken, and no control."""
    from repro.serving.workload import Request

    tr, model = spec["traffic"], spec["config"]["model"]
    params = weights.make_mamba2(seed, model, spec["config"]["padded_vocab"])
    engine = build_engine(spec, params, seed)
    if fault == "altered_token":
        _alter_tokens(engine)
    engine.warmup()
    reqs = [Request(rid=i, prompt=r.prompt, max_tokens=r.max_tokens,
                    arrival_s=r.due_s)
            for i, r in enumerate(traffic.requests(seed, seconds, tr,
                                                   model["vocab"]))]
    engine.run(reqs)
    sample = check_sample(reqs, tr, seed)
    del engine, params
    gc.collect()
    out = {"program": {"served_token_gap": served_gap(spec, seed, sample)}}
    if fault is None:
        gap = served_gap(spec, seed, sample, control=True)
        out["control"] = {"served_token_gap": gap}
        out["control_correct"] = all(c.ok for c in compare(gap, tr["limits"]))
    return out


def check_sample(reqs, tr, seed):
    """Finished requests to check, drawn from the seed with the longest
    among them, as padded (seqs, first served position, served count)."""
    done = [r for r in reqs if r.t_finished is not None and r.n_generated]
    n = tr["check_requests"]
    longest = max(done, key=lambda r: r.prompt_len + r.n_generated)
    rest = [r for r in done if r is not longest]
    rs = np.random.RandomState((seed + 1) % 2 ** 32)
    picked = [longest] + [rest[i] for i in rs.permutation(len(rest))[:n - 1]]
    width = tr["prompt"]["max"] + tr["output"]["max"]
    seqs = np.zeros((n, width), np.int32)
    first = np.zeros((n,), np.int32)
    count = np.zeros((n,), np.int32)
    for i, r in enumerate(picked):
        toks = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
        seqs[i, :len(toks)] = toks
        first[i], count[i] = r.prompt_len, r.n_generated
    return seqs, first, count


def _count_blocks(engine):
    """Wrap the engine's decode block so that each call's tokens are
    counted when the host has them: returns the list it fills with
    (perf_counter, tokens emitted) per block."""
    decode, blocks = engine._decode_block, []

    def counted(*args):
        out = decode(*args)
        n = int(np.asarray(out[5]).sum())
        blocks.append((time.perf_counter(), n))
        return out

    counted._cache_size = decode._cache_size
    engine._decode_block = counted
    return blocks


def _alter_tokens(engine):
    """A fault: each decode block's tokens come out changed for slot 0."""
    decode = engine._decode_block

    def altered(*args):
        out = list(decode(*args))
        toks = out[4]
        out[4] = toks.at[:, 0].set((toks[:, 0] + 1) % engine.cfg.vocab)
        return tuple(out)

    altered._cache_size = decode._cache_size
    engine._decode_block = altered
