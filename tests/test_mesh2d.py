"""2-D (data x model) mesh parity suite for LM-scale PPO.

The distributed seams under test, on a forced 4-device CPU 2x2 mesh:

- model-sharded PPO train_step (partial-auto shard_map: manual 'data',
  GSPMD 'model') matches the unsharded step on the SAME batch to <=1e-4;
- with --compress int8_ef, the error-feedback residual makes the cumulative
  applied update converge to the uncompressed sum (EF telescoping guarantee)
  over a multi-window run through the real train_step seam;
- TrainLoop(mesh=..., compress="int8_ef") trains end-to-end and the
  sent_compress_err_norm / per-axis grad-norm sentinels flow;
- split_actor_learner never hands out a device the data mesh owns
  (regression: async actor/learner colocated with a mesh'd learner).
"""
import jax
import pytest

from conftest import run_with_devices

from repro.launch.mesh import make_2d_mesh, parse_mesh_arg


def test_parse_mesh_arg():
    assert parse_mesh_arg("") is None
    assert parse_mesh_arg("1x1") is None
    assert parse_mesh_arg("2x2") == (2, 2)
    assert parse_mesh_arg("1x4") == (1, 4)
    assert parse_mesh_arg("4,2") == (4, 2)
    assert parse_mesh_arg("2X2") == (2, 2)
    with pytest.raises(ValueError):
        parse_mesh_arg("2x2x2")
    with pytest.raises(ValueError):
        parse_mesh_arg("abc")


def test_make_2d_mesh_validates_device_budget():
    # the in-process test sees 1 device: 1x1 builds, anything larger raises
    mesh = make_2d_mesh(1, 1)
    assert mesh.axis_names == ("data", "model")
    with pytest.raises(ValueError, match="devices"):
        make_2d_mesh(2, 1)
    with pytest.raises(ValueError, match="n_model"):
        make_2d_mesh(1, 0)


def test_make_2d_mesh_shapes_on_forced_devices():
    run_with_devices("""
import jax
from repro.launch.mesh import make_2d_mesh, mesh_devices
m22 = make_2d_mesh(2, 2)
assert dict(m22.shape) == {"data": 2, "model": 2}
m14 = make_2d_mesh(1, 4)
assert dict(m14.shape) == {"data": 1, "model": 4}
# n_data=0 infers from the device count
m41 = make_2d_mesh(0, 1)
assert dict(m41.shape) == {"data": 4, "model": 1}
assert len(mesh_devices(m22)) == 4
try:
    make_2d_mesh(4, 2)
    raise SystemExit("expected ValueError")
except ValueError:
    pass
print("shapes ok")
""", n_devices=4)


def test_lm_train_model_only_mesh_then_single_device():
    """launch/train.py on --mesh 1x4 (one data shard: no data collectives
    to trip the partial-manual partitioner), then --mesh 1x1 in the same
    process: the single-device run must not inherit the earlier mesh."""
    run_with_devices("""
import jax, numpy as np
from repro.launch import train
from repro.models import sharding as shd
for spec in ("1x4", "1x1"):
    params = train.main(["--arch", "mamba2-1.3b", "--steps", "2", "--batch",
                         "4", "--horizon", "8", "--fuse-window", "2",
                         "--mesh", spec])
    assert all(np.isfinite(np.asarray(x)).all()
               for x in jax.tree_util.tree_leaves(params)), spec
assert shd.get_global_mesh() is None
print("ok")
""", n_devices=4)


def test_split_actor_learner_excludes_mesh_devices():
    """Regression: the async runner must not pin its actor or learner onto a
    device the data mesh owns — a shared device silently serializes the
    shard_map'd program against the async streams."""
    run_with_devices("""
import jax
from repro.launch.mesh import (make_data_mesh, mesh_devices,
                               split_actor_learner)
mesh = make_data_mesh(2)
owned = mesh_devices(mesh)
actor, learner = split_actor_learner(mesh=mesh)
assert actor.id not in owned and learner.id not in owned, (
    actor, learner, owned)
assert actor.id != learner.id  # two devices remain -> still disjoint
# mesh owning every device must fail loudly, not silently co-schedule
mesh_all = make_data_mesh(4)
try:
    split_actor_learner(mesh=mesh_all)
    raise SystemExit("expected ValueError")
except ValueError:
    pass
print("split ok")
""", n_devices=4)


def test_mesh2d_parity_uncompressed():
    """Model-sharded (2x2) LM PPO train_step == unsharded train_step on the
    same fixed batch, params within 1e-4 after 3 steps.  f32 compute so the
    only differences are cross-device reduction orders."""
    run_with_devices("""
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.configs import get_smoke_config
from repro.models import backbones as bb
from repro.models import sharding as shd
from repro.algos.pg.ppo import make_lm_ppo_train_step
from repro.train.optim import adam, cross_replica
from repro.launch.mesh import make_2d_mesh, install_2d

cfg = dataclasses.replace(get_smoke_config("gemma2-2b"), unroll=True,
                          compute_dtype="float32", n_layers=2)
B, T = 8, 16
k = jax.random.PRNGKey(0)
params = bb.init_lm(k, cfg)
batch = {
    "tokens": jax.random.randint(jax.random.fold_in(k, 1), (B, T), 0,
                                 cfg.vocab),
    "actions": jax.random.randint(jax.random.fold_in(k, 2), (B, T), 0,
                                  cfg.vocab),
    "logp_old": -jnp.abs(jax.random.normal(jax.random.fold_in(k, 3), (B, T))),
    "advantage": jax.random.normal(jax.random.fold_in(k, 4), (B, T)),
    "return_": jax.random.normal(jax.random.fold_in(k, 5), (B, T)),
}

# reference: no mesh, plain adam on the full batch
shd.set_global_mesh(None)
opt_ref = adam(1e-3, grad_clip=1.0)
step_ref = jax.jit(make_lm_ppo_train_step(cfg, opt_ref, entropy_coeff=0.003,
                                          unroll_micro=True))
p_ref, o_ref = params, opt_ref.init(params)
for _ in range(3):
    p_ref, o_ref, m_ref = step_ref(p_ref, o_ref, batch)

# sharded: 2x2 mesh, model-sharded params, pmean'd grads over 'data'
mesh = install_2d(make_2d_mesh(2, 2))
pspecs = shd.param_pspecs(params, cfg)
p_sh = jax.device_put(params, shd.make_shardings(pspecs, mesh))
opt_sh = cross_replica(adam(1e-3, grad_clip=1.0), "data")
step_fn = make_lm_ppo_train_step(cfg, opt_sh, entropy_coeff=0.003,
                                 unroll_micro=True, param_pspecs=pspecs)

def step(p, o, b):
    p, o, m = step_fn(p, o, b)
    return p, o, {k2: jax.lax.pmean(v, "data") for k2, v in m.items()}

step_sh = jax.jit(jax.shard_map(step, mesh=mesh,
                                in_specs=(P(), P(), P("data")),
                                out_specs=(P(), P(), P()), check_vma=False,
                                axis_names={"data"}))
o_sh = opt_sh.init(p_sh)
for _ in range(3):
    p_sh, o_sh, m_sh = step_sh(p_sh, o_sh, batch)

flat_ref = jax.tree_util.tree_leaves_with_path(p_ref)
flat_sh = {jax.tree_util.keystr(kp): v
           for kp, v in jax.tree_util.tree_leaves_with_path(
               jax.device_get(p_sh))}
worst = 0.0
for kp, a in flat_ref:
    b = flat_sh[jax.tree_util.keystr(kp)]
    d = float(np.abs(np.asarray(a, np.float32) -
                     np.asarray(b, np.float32)).max())
    worst = max(worst, d)
    assert d <= 1e-4, (jax.tree_util.keystr(kp), d)
np.testing.assert_allclose(float(m_ref["loss"]), float(m_sh["loss"]),
                           atol=1e-4, rtol=1e-4)
print(f"parity ok, worst leaf diff {worst:.2e}")
""", n_devices=4)


def test_mesh2d_ef_cumulative_convergence():
    """EF guarantee through the real train_step seam, multi-window: with
    momentum-free SGD the cumulative applied update telescopes to the
    cumulative TRUE pmean'd gradient minus the final mean residual —
    (params_0 - params_T)/lr == sum_t pmean(grads_t) - mean_shards(r_T)."""
    run_with_devices("""
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.configs import get_smoke_config
from repro.models import backbones as bb
from repro.models import sharding as shd
from repro.algos.pg.ppo import make_lm_ppo_train_step
from repro.train.optim import (Optimizer, cross_replica, cross_replica_specs,
                               sgd)
from repro.launch.mesh import make_2d_mesh, install_2d

cfg = dataclasses.replace(get_smoke_config("gemma2-2b"), unroll=True,
                          compute_dtype="float32", n_layers=2)
LR = 1e-3
mesh = install_2d(make_2d_mesh(2, 2))
k = jax.random.PRNGKey(0)
params = bb.init_lm(k, cfg)
pspecs = shd.param_pspecs(params, cfg)
params = jax.device_put(params, shd.make_shardings(pspecs, mesh))

comp = cross_replica(sgd(LR), "data", compress="int8_ef", ef_shards=2)

# instrumented optimizer: delegates to the compressed update but ALSO
# accumulates the true (uncompressed pmean) gradient stream
def instr_init(p):
    return (comp.init(p),
            jax.tree_util.tree_map(lambda x: jnp.zeros(x.shape, jnp.float32),
                                   p))

def instr_update(grads, state, p):
    cstate, acc = state
    true = jax.tree_util.tree_map(lambda g: jax.lax.pmean(g, "data"), grads)
    acc = jax.tree_util.tree_map(lambda a, g: a + g, acc, true)
    p2, cstate, gn = comp.update(grads, cstate, p)
    return p2, (cstate, acc), gn
instr = Optimizer(instr_init, instr_update)

step_fn = make_lm_ppo_train_step(cfg, instr, entropy_coeff=0.003,
                                 unroll_micro=True, param_pspecs=pspecs)

def step(p, s, b):
    p, s, m = step_fn(p, s, b)
    return p, s, {k2: jax.lax.pmean(v, "data") for k2, v in m.items()}

spec = (cross_replica_specs("data"), P())
step_sh = jax.jit(jax.shard_map(step, mesh=mesh,
                                in_specs=(P(), spec, P("data")),
                                out_specs=(P(), spec, P()), check_vma=False,
                                axis_names={"data"}))

B, T = 8, 16
state = instr_init(params)
p = params
metrics = None
for t in range(6):  # two 3-step windows' worth of updates
    kt = jax.random.fold_in(k, 100 + t)
    batch = {
        "tokens": jax.random.randint(jax.random.fold_in(kt, 1), (B, T), 0,
                                     cfg.vocab),
        "actions": jax.random.randint(jax.random.fold_in(kt, 2), (B, T), 0,
                                      cfg.vocab),
        "logp_old": -jnp.abs(jax.random.normal(jax.random.fold_in(kt, 3),
                                               (B, T))),
        "advantage": jax.random.normal(jax.random.fold_in(kt, 4), (B, T)),
        "return_": jax.random.normal(jax.random.fold_in(kt, 5), (B, T)),
    }
    p, state, metrics = step_sh(p, state, batch)

cstate, acc = state
# compression-health metrics flow out of the train_step seam
assert float(metrics["compress_err_norm"]) > 0
assert float(metrics["grad_norm_shard_max"]) > 0
res_mean = jax.tree_util.tree_map(
    lambda r: np.asarray(r, np.float32).mean(axis=0), cstate.ef.residual)
res_norm = float(np.sqrt(sum(np.sum(np.square(np.asarray(l)))
                             for l in jax.tree_util.tree_leaves(res_mean))))
assert res_norm > 0  # quantization genuinely dropped something

applied = jax.tree_util.tree_map(
    lambda a, b: (np.asarray(a, np.float32) - np.asarray(b, np.float32)) / LR,
    jax.device_get(params), jax.device_get(p))
expect = jax.tree_util.tree_map(
    lambda a, r: np.asarray(a, np.float32) - r, jax.device_get(acc), res_mean)
for (kp, got), exp in zip(jax.tree_util.tree_leaves_with_path(applied),
                          jax.tree_util.tree_leaves(expect)):
    scale = max(np.abs(exp).max(), 1.0)
    d = np.abs(got - exp).max() / scale
    assert d <= 1e-3, (jax.tree_util.keystr(kp), d)
print(f"EF telescoping ok, |r_T|={res_norm:.3g}")
""", n_devices=4, timeout=420)


def test_trainloop_mesh_compress_end_to_end():
    """TrainLoop(mesh=..., compress='int8_ef'): the fused RL window trains
    A2C with the compressed data-axis reduction and the EF residual riding
    the train state; sent_compress_err_norm and the per-axis grad-norm
    sentinel reach the summarized log row; mis-initialized train state (no
    EF residual) fails with the clear error."""
    run_with_devices("""
import jax, numpy as np
from repro.envs import make_env
from repro.agents import make_categorical_pg_agent
from repro.models.rl_models import make_pg_mlp
from repro.samplers import ShardedSampler
from repro.algos import A2C
from repro.core.distributions import Categorical
from repro.runners import TrainLoop
from repro.runners.train_loop import split_keys
from repro.train.optim import adam
from repro.launch.mesh import make_data_mesh
from repro.telemetry import sentinels as sm

mesh = make_data_mesh(4)
env = make_env("cartpole")
model = make_pg_mlp(4, 2)
agent = make_categorical_pg_agent(model)
rng = jax.random.PRNGKey(0)
params = model.init(rng)
algo = A2C(model.apply, adam(1e-3), distribution=Categorical(2))
loop = TrainLoop(ShardedSampler(env, agent, n_envs=8, horizon=16, mesh=mesh),
                 algo, mesh=mesh, compress="int8_ef", sentinels=True)

ts = loop.algo.init_train_state(rng, params)  # wrapped algo -> EF residual
ss = loop.sampler.init(jax.random.PRNGKey(1))
_, keys = split_keys(jax.random.PRNGKey(2), 10)
ts, ss, _, infos, sents = loop.run_window(ts, ss, None, keys)
assert int(ts.step) == 10
assert all(np.isfinite(np.asarray(l, np.float32)).all()
           for l in jax.tree_util.tree_leaves(ts.params))
row = sm.summarize(sents)
assert row["sent_compress_err_norm"] > 0, row
assert row["sent_grad_norm_shard_max"] > 0, row
assert row["sent_nonfinite_params"] == 0, row

# the EF residual is genuinely per-shard state: 4 slices in the train state
from repro.train.optim import CrossReplicaState
crs = [s for s in jax.tree_util.tree_leaves(
    ts.opt_state, is_leaf=lambda x: isinstance(x, CrossReplicaState))
    if isinstance(s, CrossReplicaState)]
assert len(crs) == 1
assert all(l.shape[0] == 4
           for l in jax.tree_util.tree_leaves(crs[0].ef.residual))

# mis-initialized train state: plain opt state, clear error
ts_bad = algo.init_train_state(rng, params)  # UNwrapped algo
loop2 = TrainLoop(ShardedSampler(env, agent, n_envs=8, horizon=16, mesh=mesh),
                  algo, mesh=mesh, compress="int8_ef")
try:
    loop2.run_window(ts_bad, ss, None, keys)
    raise SystemExit("expected ValueError")
except ValueError as e:
    assert "init_train_state" in str(e), e
print("trainloop compress ok")
""", n_devices=4)


def test_trainloop_compress_requires_mesh():
    from repro.runners import TrainLoop
    from repro.algos import A2C

    class _Algo:  # enough to pass BatchSpec validation, no mesh given
        batch_spec = A2C.batch_spec

    with pytest.raises(ValueError, match="mesh"):
        TrainLoop(object(), _Algo(), compress="int8_ef")
