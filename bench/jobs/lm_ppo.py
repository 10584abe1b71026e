"""LM-PPO training job: the fused (rollout + GAE + PPO update) window of
``launch/train.py``'s single-device path, driven for the measured window.

Set-up makes the weights from the seed, builds the window program with
params and Adam state donated, and makes its first call: that call
compiles (or reads the compile cache) and runs the window's first steps,
whose losses, rollout tokens and resulting state the check compares with
the plain reference once the measured window has closed.  The same program
and state then run back to back for ``--seconds``, the host reading the
loss after every call as the program's own loop does.

Traffic parameters (``bench/traffic/<name>.json``): batch, horizon,
fuse_window, the optimiser and PPO settings, and the limits of the check.
"""
from __future__ import annotations

import functools
import time

import numpy as np

from bench import common, weights


def build_window(cfg, env, tr, *, fault=None):
    """The program's fused window (``launch/train.py``, single-device
    ``--fuse-window``) over its own rollout and train step, returning the
    per-step losses and the rollout tokens the check needs.  ``fault``
    (tests only): "half_batch" trains on the first half of the rows,
    "altered_token" changes the first row's sampled tokens as they come
    out."""
    import jax
    import jax.numpy as jnp
    from repro.algos.pg.gae import gae_associative
    from repro.algos.pg.ppo import make_lm_ppo_train_step
    from repro.launch.train import make_lm_rollout
    from repro.train.optim import adam

    opt = adam(tr["lr"], b1=tr["b1"], b2=tr["b2"], eps=tr["adam_eps"],
               grad_clip=tr["grad_clip"])
    rollout = jax.jit(make_lm_rollout(cfg, env, tr["batch"], tr["horizon"]))
    train_step = jax.jit(make_lm_ppo_train_step(
        cfg, opt, clip_eps=tr["clip_eps"], value_coeff=tr["value_coeff"],
        entropy_coeff=tr["entropy_coeff"]))

    def build_batch(traj, v_last):
        adv, ret = gae_associative(traj["reward"], traj["value"], v_last,
                                   traj["done"], gamma=tr["gamma"],
                                   lam=tr["lam"])
        adv = (adv - adv.mean()) / (adv.std() + 1e-8)
        tm = lambda x: jnp.swapaxes(x, 0, 1)  # noqa: E731
        return {"tokens": tm(traj["tokens"]), "actions": tm(traj["actions"]),
                "logp_old": tm(traj["logp"]), "advantage": tm(adv),
                "return_": tm(ret)}

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def fused_window(params, opt_state, ks):
        def body(carry, k):
            p, o = carry
            traj, v_last = rollout(p, k)
            batch = build_batch(traj, v_last)
            fed = batch
            if fault == "half_batch":
                fed = jax.tree_util.tree_map(lambda x: x[:x.shape[0] // 2],
                                             batch)
            p, o, metrics = train_step(p, o, fed)
            actions = batch["actions"]
            if fault == "altered_token":
                actions = actions.at[0].set((actions[0] + 1) % cfg.vocab)
            return (p, o), {"loss": metrics["loss"],
                            "obs": batch["tokens"], "actions": actions,
                            "logp": batch["logp_old"]}
        (params, opt_state), out = jax.lax.scan(body, (params, opt_state), ks)
        return params, opt_state, out

    return opt, fused_window


def norm_gaps(prog: dict, ref: dict, floor_share: float = 0.0,
              gate: dict = None):
    """Worst leaf of |prog norm - ref norm| / max(ref norm, median ref
    norm).  With ``gate``, leaves whose gate norm is under ``floor_share``
    of the median gate norm are left out.  Returns (gap, leaf)."""
    med = float(np.median([float(v) for v in ref.values()]))
    keep = list(ref)
    if gate is not None:
        gmed = float(np.median([float(v) for v in gate.values()]))
        keep = [k for k in ref if float(gate[k]) >= floor_share * gmed]
    worst = max(keep, key=lambda k: abs(float(prog[k]) - float(ref[k]))
                / max(float(ref[k]), med))
    return (abs(float(prog[worst]) - float(ref[worst]))
            / max(float(ref[worst]), med), worst)


def model_config(spec):
    from repro.models.config import ModelConfig

    return ModelConfig(**spec["config"]["model"])


def setup(spec: dict, seed: int, *, fault=None):
    """Weights from the seed, the window program and its state, and the
    window's first call: the steps the check follows.  Returns the state
    to hand on to the measured window and what the check needs."""
    import types

    import jax
    import jax.numpy as jnp
    from repro.envs.token_lm import make_token_lm
    from repro.launch.mesh import install
    from repro.runners.train_loop import split_keys
    from repro.telemetry import trace

    from bench.reference.lm_ppo import leaf_norms

    tr, cfg = spec["traffic"], model_config(spec)
    W = tr["fuse_window"]
    install(None)
    tracer = trace.configure(None)
    env = make_token_lm(vocab=cfg.vocab, episode_len=tr["horizon"])
    opt, window = build_window(cfg, env, tr, fault=fault)
    if fault == "unchanged":
        window = _unchanged(window)
    tracer.watch_jit("lm.fused_window", window)

    make = functools.partial(weights.make_mamba2, seed,
                             spec["config"]["model"],
                             spec["config"]["padded_vocab"])
    params = make()
    opt_state = jax.jit(opt.init)(params)
    rng = jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 31),
                             seed // 2 ** 31)
    rng, ks = split_keys(rng, W)
    # the first call compiles and runs the steps the check follows; the
    # loss readback the measured loop makes is warmed up here too
    params, opt_state, first = window(params, opt_state, ks)
    float(first["loss"][-1])
    first = jax.device_get(first)
    prog = {"loss": [float(x) for x in first["loss"]],
            "logp": [np.asarray(first["logp"][i]) for i in range(W)],
            "mu_norms": jax.device_get(jax.jit(leaf_norms)(opt_state.mu)),
            "change_norms": jax.device_get(jax.jit(
                lambda p, p0: leaf_norms(jax.tree_util.tree_map(
                    lambda a, b: a - b, p, p0)))(params, make()))}
    steps_in = [(jnp.asarray(first["obs"][i]),
                 jnp.asarray(first["actions"][i])) for i in range(W)]
    tracer.poll_recompiles()
    return types.SimpleNamespace(
        window=window, params=params, opt_state=opt_state, rng=rng,
        tracer=tracer, make=make, prog=prog, steps_in=steps_in)


def follow(spec: dict, s, *, quant: bool = False) -> dict:
    """The plain reference (``quant``: its float8 control) through the
    steps of the window's first call."""
    from bench.reference import lm_ppo as reference

    return reference.follow(s.make, s.steps_in, spec["traffic"],
                            vocab=spec["config"]["model"]["vocab"],
                            quant=quant)


def run(run: common.Run, spec: dict, *, fault=None) -> None:
    """One run of the cell.  ``fault`` (tests only) breaks the timed path:
    "unchanged" returns the state the step was given; "half_batch" and
    "altered_token" as in :func:`build_window`."""
    from repro.runners.train_loop import split_keys

    tr = spec["traffic"]
    B, H, W = tr["batch"], tr["horizon"], tr["fuse_window"]
    s = setup(spec, run.seed, fault=fault)
    window, params, opt_state, rng = s.window, s.params, s.opt_state, s.rng
    s.params = s.opt_state = None

    run.setup_done()
    ends = []
    with run.window():
        t0 = time.perf_counter()
        while True:
            rng, ks = split_keys(rng, W)
            with common.span("window_call"):
                params, opt_state, out = window(params, opt_state, ks)
            with common.span("readback"):
                float(out["loss"][-1])
            ends.append(time.perf_counter())
            run.poll_trace()
            if ends[-1] - t0 >= run.seconds:
                break
        t1 = ends[-1]
    recompiles = s.tracer.poll_recompiles()
    run.read_memory()
    del params, opt_state, out

    steps = len(ends) * W
    call_s = np.diff([t0] + ends)
    run.record.update(
        attempted=steps, failed=0, window_s=t1 - t0, steps=steps,
        call_ends=ends, call_s_median=float(np.median(call_s)),
        call_s_max=float(np.max(call_s)),
        tokens=steps * B * H, recompiles=recompiles, batch=B, horizon=H,
        fuse_window=W, model=spec["config"]["model"],
        padded_vocab=spec["config"]["padded_vocab"])
    run.checks.extend(compare(s.prog, follow(spec, s), tr["limits"]))


def calibrate(spec: dict, seed: int, *, fault=None) -> dict:
    """The numbers compared, for the program and for the control (the
    reference in float8 in the program's place), on one seed, and the
    control's verdict by :func:`compare` against the cell's limits; with
    ``fault``, for the program so broken, and no control."""
    import gc

    s = setup(spec, seed, fault=fault)
    s.params = s.opt_state = None
    gc.collect()
    ref = follow(spec, s)
    out = {"program": gaps(s.prog, ref)}
    if fault is None:
        control = follow(spec, s, quant=True)
        out["control"] = gaps(control, ref)
        out["control_correct"] = all(
            c.ok for c in compare(control, ref, spec["traffic"]["limits"]))
        out["program_worst_leaf"] = {
            name: norm_gaps(s.prog[name], ref[name])[1]
            for name in ("mu_norms", "change_norms")}
    return out


def gaps(prog: dict, ref: dict) -> dict:
    """Each number compared: the widest rollout log-prob gap, the largest
    relative loss gap over the steps, and the worst leaf's gap of norms of
    Adam's first moment and of the parameters' change (leaves whose
    reference moment is under a thousandth of the median leaf's left out
    of the change)."""
    change, _ = norm_gaps(prog["change_norms"], ref["change_norms"],
                          floor_share=1e-3, gate=ref["mu_norms"])
    return {
        "rollout_logp_gap": max(float(np.max(np.abs(a - b)))
                                for a, b in zip(prog["logp"], ref["logp"])),
        "rollout_logp_mean_gap": float(np.mean(
            [np.mean(np.abs(a - b)) for a, b in zip(prog["logp"],
                                                   ref["logp"])])),
        "loss_rel_gap": max(abs(a - b) / abs(b)
                            for a, b in zip(prog["loss"], ref["loss"])),
        "adam_mu_norm_gap": norm_gaps(prog["mu_norms"], ref["mu_norms"])[0],
        "param_change_norm_gap": change}


def compare(prog: dict, ref: dict, limits: dict):
    """The numbers that decide ``correct`` (those the cell's traffic file
    gives a limit), each beside its limit."""
    values = gaps(prog, ref)
    return [common.Check(name, values[name], limit)
            for name, limit in limits.items()]


def _unchanged(window):
    """The window with a fault: it returns the state it was given."""
    import jax

    def broken(params, opt_state, ks):
        keep = jax.tree_util.tree_map(lambda x: x.copy(), (params, opt_state))
        _, _, out = window(params, opt_state, ks)
        return keep[0], keep[1], out

    broken._cache_size = window._cache_size
    return broken
