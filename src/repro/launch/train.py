"""End-to-end LM-policy RL training driver (example app + launcher target).

The RLHF-style regime from DESIGN.md §3: the policy IS a language model over
the token-MDP environment; batched action selection is LM decoding with a
KV/SSM cache (the paper's serving path), and the PPO update is the paper's
training path — the same train_step the multi-pod dry-run lowers.

CPU-runnable at smoke scale:
  PYTHONPATH=src python -m repro.launch.train --arch gemma2-2b --smoke \
      --steps 50 --batch 16 --horizon 32

2-D (data x model) mesh mode — model-parallel LM PPO with the gradient
all-reduce over 'data' optionally routed through the int8 error-feedback
compressor (train/compress.py):
  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
  PYTHONPATH=src python -m repro.launch.train --mesh 2x2 --compress --smoke
``--mesh`` defaults to $REPRO_MESH so CI legs select it without editing
commands.  On a pod, drop --smoke (the launcher generates per-pod
jax.distributed init; see launcher.py).
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import os
import time

import jax
import jax.numpy as jnp

from ..configs import get_config, get_smoke_config
from ..models import backbones as bb
from ..models.config import ModelConfig
from ..envs.token_lm import make_token_lm
from ..algos.pg.gae import gae_associative
from ..algos.pg.ppo import make_lm_ppo_train_step
from ..telemetry import trace
from ..train.optim import adam
from ..train.checkpoint import save_checkpoint, restore_checkpoint, latest_step
from ..utils.compile_cache import enable_compile_cache
from ..utils.logger import Logger
from ..kernels import registry as kernel_registry

F32 = jnp.float32


def make_lm_rollout(cfg: ModelConfig, env, batch: int, horizon: int,
                    temperature: float = 1.0):
    """Batched action selection with the serving path: one decode_step per
    env step, cache carried through a lax.scan."""
    V = env.action_space.n

    @jax.named_scope("lm_rollout")   # the rollout's device ops carry it
    def rollout(params, rng):
        k_env, k_roll = jax.random.split(rng)
        env_state, obs = jax.vmap(env.reset)(jax.random.split(k_env, batch))
        cache = bb.init_cache(cfg, batch, horizon + 1)

        def step(carry, k):
            env_state, obs, cache = carry
            k_act, k_step = jax.random.split(k)
            hidden, cache = bb.decode_step(params, cache, obs, cfg)
            logits = bb.lm_logits(params, hidden, cfg)[:, 0, :V].astype(F32)
            value = bb.value_out(params, hidden)[:, 0]
            action = jax.random.categorical(k_act, logits / temperature)
            logp = jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                                       action[:, None], axis=1)[:, 0]
            env_state, obs2, reward, done, _ = jax.vmap(env.step)(
                env_state, action, jax.random.split(k_step, batch))
            out = {"tokens": obs, "actions": action, "logp": logp,
                   "value": value, "reward": reward, "done": done}
            return (env_state, obs2, cache), out

        (_, obs_last, cache), traj = jax.lax.scan(
            step, (env_state, obs, cache), jax.random.split(k_roll, horizon))
        # bootstrap value of the last obs
        hidden, _ = bb.decode_step(params, cache, obs_last, cfg)
        v_last = bb.value_out(params, hidden)[:, 0]
        return traj, v_last

    return rollout


def run_mesh(args, cfg, env, logger, tracer, rng, mesh_shape, shutdown):
    """2-D (data x model) mesh driver.

    'model' is a GSPMD auto axis: backbone params/activations shard through
    models/sharding.py rules (param_pspecs at init, `constrain` calls in the
    forward).  'data' is MANUAL inside the shard_map'd window: each data
    shard runs its own rollout (decorrelated by fold_in(axis_index)) on a
    local batch slice, and the gradient all-reduce is the explicit
    cross_replica collective — which is exactly the hook that lets
    --compress route it through the int8 error-feedback compressor.
    """
    from jax.sharding import PartitionSpec as P

    from ..models import sharding as shd
    from ..train.optim import cross_replica, cross_replica_specs
    from ..train.compress import wire_bytes
    from .mesh import make_2d_mesh, install_2d

    n_data, n_model = mesh_shape
    mesh = install_2d(make_2d_mesh(n_data, n_model))
    # XLA's while-loop partitioner can't scan over auto-sharded xs inside a
    # partial-auto shard_map (model-sharded CARRIES are fine; model-sharded
    # stacked block params as scan xs abort with IsManualSubgroup) — unroll
    # the layer stack so per-layer weights are slices of the sharded stack
    cfg = dataclasses.replace(cfg, unroll=True)
    if args.batch % n_data:
        raise SystemExit(
            f"--batch {args.batch} must divide by the data axis ({n_data})")
    local_batch = args.batch // n_data
    print(f"mesh {n_data}x{n_model} over ('data','model'), "
          f"local batch {local_batch}, compress={args.compress or 'off'}")

    k_init, rng = jax.random.split(rng)
    init = functools.partial(bb.init_lm, cfg=cfg)
    pspecs = shd.param_pspecs(jax.eval_shape(init, k_init), cfg)
    # initialized in place, sharded: no device holds the whole model first
    params = jax.jit(init, out_shardings=shd.make_shardings(pspecs, mesh))(
        k_init)
    if args.compress:
        wb = wire_bytes(params)
        print(f"int8 all-reduce payload: {wb['int8_bytes']:,} B/step "
              f"(fp32 {wb['fp32_bytes']:,} B, {wb['ratio']:.2f}x reduction)")

    # over one data shard every 'data' collective is the identity, and
    # XLA's partial-manual partitioner refuses an all-reduce over a size-1
    # manual axis (RET_CHECK IsManualSubgroup): leave them out
    opt = adam(args.lr, grad_clip=1.0)
    if n_data > 1 or args.compress:
        opt = cross_replica(opt, "data", compress=args.compress,
                            ef_shards=n_data)
    opt_state = opt.init(params)
    ts_spec = cross_replica_specs("data") if args.compress else P()

    rollout = make_lm_rollout(cfg, env, local_batch, args.horizon)
    # unroll_micro for the same reason as the layer unroll above: the
    # microbatch-accumulation scan's grad body trips the partial-auto
    # while-loop partitioner
    train_step = make_lm_ppo_train_step(cfg, opt, entropy_coeff=0.003,
                                        param_pspecs=pspecs,
                                        unroll_micro=True)

    def build_batch(traj, v_last):
        # identical math to the serial path, shard-local: advantages are
        # normalized over the LOCAL batch (documented semantic difference —
        # the global batch is never materialized on one device)
        adv, ret = gae_associative(traj["reward"], traj["value"], v_last,
                                   traj["done"], gamma=0.99, lam=0.95)
        adv = (adv - adv.mean()) / (adv.std() + 1e-8)
        tm = lambda x: jnp.swapaxes(x, 0, 1)
        return {"tokens": tm(traj["tokens"]), "actions": tm(traj["actions"]),
                "logp_old": tm(traj["logp"]), "advantage": tm(adv),
                "return_": tm(ret)}

    def window(params, opt_state, ks, sid):
        # shard identity arrives as a P('data')-sharded iota: axis_index on a
        # manual axis lowers to PartitionId, which the partial-auto (GSPMD
        # 'model') partitioner refuses to place.  The window is a PYTHON loop
        # (not lax.scan): model-sharded params as a while-loop carry trip the
        # same partitioner limitation as the layer/microbatch scans — the
        # window still compiles to ONE program, just unrolled.
        me = sid[0]
        metrics = {}
        for i in range(ks.shape[0]):
            # one data shard rolls out on the single-device path's keys
            k = ks[i] if n_data == 1 else jax.random.fold_in(ks[i], me)
            traj, v_last = rollout(params, k)
            batch = build_batch(traj, v_last)
            params, opt_state, metrics = train_step(params, opt_state, batch)
            metrics = dict(metrics, avg_reward=jnp.mean(traj["reward"]))
        if n_data > 1:
            metrics = {name: jax.lax.pmean(v, "data")
                       for name, v in metrics.items()}
        return params, opt_state, metrics

    mesh_window = jax.jit(jax.shard_map(
        window, mesh=mesh,
        in_specs=(P(), ts_spec, P(), P("data")),
        out_specs=(P(), ts_spec, P()),
        check_vma=False, axis_names={"data"}))
    tracer.watch_jit("lm.mesh_window", mesh_window)
    shard_ids = jnp.arange(n_data, dtype=jnp.uint32)

    from ..runners.train_loop import split_keys
    start = 0
    if args.restore and args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
        (params, opt_state), manifest = restore_checkpoint(
            args.ckpt_dir, (params, opt_state))
        start = manifest["step"]
        print(f"restored step {start}")

    t0 = time.time()
    step = start
    while step < args.steps:
        chunk = min(args.fuse_window, args.steps - step)
        if args.ckpt_dir and args.ckpt_interval:
            nxt = step + args.ckpt_interval - (step % args.ckpt_interval)
            chunk = min(chunk, nxt - step)
        rng, ks = split_keys(rng, chunk)
        with tracer.span("mesh_window", step=step, iters=chunk):
            params, opt_state, metrics = mesh_window(params, opt_state, ks,
                                                     shard_ids)
        step += chunk
        sps = args.batch * args.horizon * chunk / max(time.time() - t0, 1e-9)
        t0 = time.time()
        row = {"avg_reward": float(metrics["avg_reward"]),
               "loss": float(metrics["loss"]),
               "entropy": float(metrics["entropy"]),
               "samples_per_sec": sps}
        if "compress_err_norm" in metrics:
            row["compress_err_norm"] = float(metrics["compress_err_norm"])
            row["grad_norm_shard_max"] = float(metrics["grad_norm_shard_max"])
        with tracer.span("log", step=step):
            logger.record(step, row)
        tracer.poll_recompiles()
        tracer.memory_snapshot(f"window_{step}")
        if args.ckpt_dir and args.ckpt_interval and \
                step % args.ckpt_interval == 0:
            with tracer.span("checkpoint", step=step):
                save_checkpoint(args.ckpt_dir, step, (params, opt_state))
    shutdown()
    return params


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-2b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--horizon", type=int, default=32)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-dir", default=None)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-interval", type=int, default=0)
    ap.add_argument("--restore", action="store_true")
    ap.add_argument("--fuse-window", type=int, default=1,
                    help="compile this many (rollout + update) steps into ONE "
                         "lax.scan program (the runners' TrainLoop fusion); "
                         "logs/checkpoints land on window boundaries")
    ap.add_argument("--mesh", default=os.environ.get("REPRO_MESH", ""),
                    help="2-D mesh spec 'DATAxMODEL' (e.g. '2x2', '1x4'); "
                         "'1x1'/'' runs the single-device path.  Defaults "
                         "to $REPRO_MESH.  Requires DATA*MODEL local devices "
                         "(CPU: XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N)")
    ap.add_argument("--compress", nargs="?", const="int8_ef", default=None,
                    choices=["int8_ef"],
                    help="compress the data-axis gradient all-reduce "
                         "(int8 + error feedback); requires --mesh")
    ap.add_argument("--kernels", default=None,
                    help="kernel backend spec (REPRO_KERNELS syntax: 'ref', "
                         "'interpret', 'attention=pallas,ssd=ref', ...); "
                         "installed before any program is traced")
    ap.add_argument("--profile", nargs="?", const="", default=None,
                    metavar="DIR",
                    help="capture a jax.profiler trace of the whole run into "
                         "DIR (default <log-dir>/profile) — loadable in "
                         "perfetto / tensorboard; host phases appear as the "
                         "telemetry span annotations")
    return ap.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    return run(args, cfg)


def run(args, cfg: ModelConfig):
    """Train ``cfg`` as the LM policy under the parsed ``args`` (the CLI's
    ``--arch/--smoke`` choose the config in :func:`main`; callers may pass
    any config, e.g. one cut in depth to fit a chip)."""
    enable_compile_cache()

    # host-side telemetry: spans + recompile events to trace.jsonl when a
    # log dir exists, in-memory ring otherwise
    tracer = trace.configure(os.path.join(args.log_dir, "trace.jsonl")
                             if args.log_dir else None)
    profile_dir = None
    if args.profile is not None:
        profile_dir = args.profile or os.path.join(args.log_dir or ".",
                                                   "profile")
        jax.profiler.start_trace(profile_dir)

    if args.kernels:
        kernel_registry.set_env(args.kernels)
    print(f"kernel backends: {kernel_registry.describe()}")
    env = make_token_lm(vocab=cfg.vocab, episode_len=args.horizon)
    logger = Logger(args.log_dir)
    rng = jax.random.PRNGKey(args.seed)

    def _shutdown():
        tracer.poll_recompiles()
        tracer.memory_snapshot("end_of_run")
        if profile_dir is not None:
            jax.profiler.stop_trace()
            print(f"profiler trace written to {profile_dir}")

    from .mesh import install, parse_mesh_arg
    mesh_shape = parse_mesh_arg(args.mesh)
    if args.compress and mesh_shape is None:
        raise SystemExit("--compress requires --mesh DATAxMODEL "
                         "(e.g. --mesh 2x2)")
    if mesh_shape is not None:
        return run_mesh(args, cfg, env, logger, tracer, rng, mesh_shape,
                        _shutdown)
    # a mesh an earlier run in this process installed must not partition
    # this single-device program (Mosaic kernels cannot be auto-partitioned)
    install(None)

    k_init, rng = jax.random.split(rng)
    params = bb.init_lm(k_init, cfg)
    opt = adam(args.lr, grad_clip=1.0)
    opt_state = opt.init(params)
    rollout = jax.jit(make_lm_rollout(cfg, env, args.batch, args.horizon))
    train_step = jax.jit(make_lm_ppo_train_step(cfg, opt, entropy_coeff=0.003))
    tracer.watch_jit("lm.rollout", rollout)
    tracer.watch_jit("lm.train_step", train_step)

    start = 0
    if args.restore and args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
        (params, opt_state), manifest = restore_checkpoint(
            args.ckpt_dir, (params, opt_state))
        start = manifest["step"]
        print(f"restored step {start}")

    @jax.jit
    def build_batch(traj, v_last):
        # time-major (T, B) -> GAE -> batch-major (B, T) for the train step
        adv, ret = gae_associative(traj["reward"], traj["value"], v_last,
                                   traj["done"], gamma=0.99, lam=0.95)
        adv = (adv - adv.mean()) / (adv.std() + 1e-8)
        tm = lambda x: jnp.swapaxes(x, 0, 1)
        return {"tokens": tm(traj["tokens"]), "actions": tm(traj["actions"]),
                "logp_old": tm(traj["logp"]), "advantage": tm(adv),
                "return_": tm(ret)}

    if args.fuse_window > 1:
        # the TrainLoop fusion at LM scale: rollout (serving path) + GAE +
        # PPO update scanned over the window — one device program, metrics
        # stacked and read back only at window boundaries.  The jitted
        # rollout/train_step above inline into the outer jit, so both
        # dispatch modes run the exact same per-step program.
        from ..runners.train_loop import split_keys

        # params and optimizer state are donated: the window's outputs
        # replace them, so one copy of each is resident, not two
        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def fused_window(params, opt_state, ks):
            def body(carry, k):
                p, o = carry
                traj, v_last = rollout(p, k)
                batch = build_batch(traj, v_last)
                p, o, metrics = train_step(p, o, batch)
                metrics = dict(metrics,
                               avg_reward=jnp.mean(traj["reward"]))
                return (p, o), metrics
            (params, opt_state), ms = jax.lax.scan(
                body, (params, opt_state), ks)
            return params, opt_state, jax.tree_util.tree_map(
                lambda x: x[-1], ms)

        tracer.watch_jit("lm.fused_window", fused_window)
        t0 = time.time()
        step = start
        while step < args.steps:
            chunk = min(args.fuse_window, args.steps - step)
            if args.ckpt_dir and args.ckpt_interval:
                nxt = step + args.ckpt_interval - (step % args.ckpt_interval)
                chunk = min(chunk, nxt - step)
            rng, ks = split_keys(rng, chunk)
            with tracer.span("fused_window", step=step, iters=chunk):
                params, opt_state, metrics = fused_window(params, opt_state,
                                                          ks)
            step += chunk
            sps = args.batch * args.horizon * chunk / max(
                time.time() - t0, 1e-9)
            t0 = time.time()
            with tracer.span("log", step=step):
                logger.record(step, {
                    "avg_reward": float(metrics["avg_reward"]),
                    "loss": float(metrics["loss"]),
                    "entropy": float(metrics["entropy"]),
                    "samples_per_sec": sps,
                })
            tracer.poll_recompiles()
            tracer.memory_snapshot(f"window_{step}")
            if args.ckpt_dir and args.ckpt_interval and \
                    step % args.ckpt_interval == 0:
                with tracer.span("checkpoint", step=step):
                    save_checkpoint(args.ckpt_dir, step, (params, opt_state))
        _shutdown()
        return params

    t0 = time.time()
    for step in range(start, args.steps):
        rng, k = jax.random.split(rng)
        with tracer.span("rollout", step=step):
            traj, v_last = rollout(params, k)
        with tracer.span("update", step=step):
            batch = build_batch(traj, v_last)
            params, opt_state, metrics = train_step(params, opt_state, batch)
        if (step + 1) % 10 == 0 or step == args.steps - 1:
            sps = args.batch * args.horizon * 10 / max(time.time() - t0, 1e-9)
            t0 = time.time()
            with tracer.span("log", step=step + 1):
                logger.record(step + 1, {
                    "avg_reward": float(jnp.mean(traj["reward"])),
                    "loss": float(metrics["loss"]),
                    "entropy": float(metrics["entropy"]),
                    "samples_per_sec": sps,
                })
            tracer.poll_recompiles()
            tracer.memory_snapshot(f"step_{step + 1}")
        if args.ckpt_dir and args.ckpt_interval and \
                (step + 1) % args.ckpt_interval == 0:
            with tracer.span("checkpoint", step=step + 1):
                save_checkpoint(args.ckpt_dir, step + 1, (params, opt_state))
    _shutdown()
    return params


if __name__ == "__main__":
    main()
