"""Telemetry subsystem: sentinels don't perturb training (bit-identity),
nan_guard pinpoints the first bad in-window iteration, the recompile
detector fires on shape drift, sharded sentinels psum/pmean to global
values on a forced 4-device mesh, and the sinks (JSONL / CSV / tfevents)
round-trip their schemas — including the CSV field-drift + restart-append
fix for the seed logger."""
import csv
import json
import os
import struct

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_with_devices

from repro.envs import make_env
from repro.agents import make_categorical_pg_agent
from repro.models.rl_models import make_pg_mlp
from repro.samplers import SerialSampler
from repro.algos import A2C
from repro.core.distributions import Categorical
from repro.runners import TrainLoop, OnPolicyRunner
from repro.runners.train_loop import split_keys
from repro.train.optim import adam
from repro.telemetry import trace, sentinels as sentinels_mod
from repro.telemetry.metrics import (MetricsRegistry, _masked_crc, _tb_record)
from repro.telemetry.sentinels import NonFiniteError
from repro.utils.logger import Logger


class _Null:
    def record(self, *a, **k):
        pass


def _a2c_pieces(rng):
    env = make_env("cartpole")
    model = make_pg_mlp(4, 2)
    agent = make_categorical_pg_agent(model)
    algo = A2C(model.apply, adam(1e-3), distribution=Categorical(2))
    sampler = SerialSampler(env, agent, n_envs=8, horizon=16)
    return model, agent, algo, sampler


def _leaf_bytes(params):
    return [np.asarray(x).tobytes() for x in jax.tree_util.tree_leaves(params)]


# -- bit-identity: sentinels are pure reads ----------------------------------

def test_sentinels_bit_identical_params(rng):
    """Enabling sentinels adds stacked scan outputs but must not change a
    single parameter bit — fused+sentinels == fused bare == unfused+sentinels
    on the identical key stream."""
    model, _, algo, sampler = _a2c_pieces(rng)
    params = model.init(rng)
    _, keys = split_keys(jax.random.PRNGKey(2), 6)

    results = {}
    for tag, kw in (("fused_sent", dict(fuse=True, sentinels=True)),
                    ("fused_bare", dict(fuse=True)),
                    ("unfused_sent", dict(fuse=False, sentinels=True))):
        loop = TrainLoop(sampler, algo, **kw)
        ts = algo.init_train_state(rng, params)
        ts, _, _, infos, sents = loop.run_window(
            ts, sampler.init(jax.random.PRNGKey(1)), None, keys)
        results[tag] = (_leaf_bytes(ts.params), sents, infos)

    assert results["fused_sent"][0] == results["fused_bare"][0]
    assert results["fused_sent"][0] == results["unfused_sent"][0]
    assert results["fused_bare"][1] is None            # off -> no sentinel ys

    sents = results["fused_sent"][1]
    assert sents.loss.shape == (6,)
    row = sentinels_mod.summarize(sents)
    assert row["sent_window_iters"] == 6
    assert row["sent_env_steps"] == 6 * 8 * 16
    assert row["sent_nonfinite_params"] == 0
    assert row["sent_grad_norm"] > 0 and np.isfinite(row["sent_param_norm"])
    # sentinel loss IS the OptInfo loss, not a recomputation
    np.testing.assert_array_equal(np.asarray(sents.loss),
                                  np.asarray(results["fused_sent"][2].loss))


# -- nan_guard ---------------------------------------------------------------

def test_nan_guard_reports_first_bad_iteration(rng):
    """An lr schedule that goes inf at the 3rd update poisons params at
    window index 2; nan_guard must name exactly that iteration instead of
    handing back a fully-eaten window."""
    env = make_env("cartpole")
    model = make_pg_mlp(4, 2)
    agent = make_categorical_pg_agent(model)
    algo = A2C(model.apply,
               adam(lambda step: jnp.where(step >= 3, jnp.inf, 1e-3)),
               distribution=Categorical(2))
    sampler = SerialSampler(env, agent, n_envs=8, horizon=16)
    runner = OnPolicyRunner(sampler, algo, n_iterations=6, log_interval=6,
                            logger=_Null(), nan_guard=True)
    with pytest.raises(NonFiniteError) as ei:
        runner.run(rng)
    assert ei.value.iteration == 2
    assert ei.value.n_bad > 0
    guards = [e for e in trace.get_tracer().events if e["kind"] == "nan_guard"]
    assert guards and guards[-1]["iteration"] == 2


# -- recompile detector ------------------------------------------------------

def test_recompile_detector_fires_on_shape_change():
    t = trace.Tracer()
    f = jax.jit(lambda x: x * 2.0)
    t.watch_jit("f", f)
    f(jnp.ones((4,)))
    assert t.poll_recompiles() == 1            # first compile counts
    f(jnp.ones((4,)))
    assert t.poll_recompiles() == 0            # cache hit -> silent
    f(jnp.ones((8,)))                          # shape drift
    assert t.poll_recompiles() == 1
    ev = [e for e in t.events if e["kind"] == "recompile"]
    assert [e["cache_size"] for e in ev] == [1, 2]
    assert all(e["name"] == "f" for e in ev)


# -- sharded sentinels -------------------------------------------------------

def test_sharded_sentinels_reduce_to_global_values():
    """On the 4-device mesh: extensive sentinels (env_steps, replay fill)
    psum to the global value, replicated ones (loss, norms) match the serial
    loop on identical rollouts."""
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

mesh = make_data_mesh(4)
env = make_env("cartpole")
model = make_pg_mlp(4, 2)
agent = make_categorical_pg_agent(model)
rng = jax.random.PRNGKey(0)
params = model.init(rng)
algo = A2C(model.apply, adam(1e-3), distribution=Categorical(2))

def run(mesh_arg):
    sampler = ShardedSampler(env, agent, n_envs=8, horizon=16, mesh=mesh)
    loop = TrainLoop(sampler, algo, mesh=mesh_arg, sentinels=True)
    ts = algo.init_train_state(rng, params)
    ss = sampler.init(jax.random.PRNGKey(1))
    _, keys = split_keys(jax.random.PRNGKey(2), 5)
    ts, ss, _, infos, sents = loop.run_window(ts, ss, None, keys)
    return sents

sh, ref = run(mesh), run(None)
# extensive: psum over 4 shards of 2 local envs == global 8 envs x 16 steps
np.testing.assert_array_equal(np.asarray(sh.env_steps), [8 * 16] * 5)
np.testing.assert_array_equal(np.asarray(sh.env_steps),
                              np.asarray(ref.env_steps))
# replicated: pmean'd norms/loss equal the serial global-batch run
for field in ("loss", "grad_norm", "param_norm", "update_norm"):
    np.testing.assert_allclose(np.asarray(getattr(sh, field)),
                               np.asarray(getattr(ref, field)),
                               atol=2e-5, rtol=2e-4)
assert int(np.asarray(sh.nonfinite_params).sum()) == 0
print("sharded sentinels ok")
""", n_devices=4)


# -- sink schemas ------------------------------------------------------------

def test_tracer_jsonl_round_trip(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    t = trace.Tracer(path)
    t.emit("custom", "hello", answer=42)
    with t.span("phase", iteration=3):
        pass
    t.close()
    with open(path) as f:
        events = [json.loads(line) for line in f]
    assert [e["kind"] for e in events] == ["custom", "span"]
    assert events[0]["answer"] == 42
    assert events[1]["name"] == "phase" and events[1]["iteration"] == 3
    assert events[1]["dur_s"] >= 0
    assert all("ts" in e for e in events)
    # the in-memory ring saw the same events
    assert [e["kind"] for e in t.events] == ["custom", "span"]


def test_span_ids_parents_and_self_seconds():
    t = trace.Tracer()
    with t.span("outer", rid=7) as attrs:
        with t.span("inner"):
            pass
        with t.span("inner"):
            pass
        attrs["n"] = 2                       # known only at the end
    with t.span("outer"):
        pass
    ev = [e for e in t.events if e["kind"] == "span"]
    assert [e["name"] for e in ev] == ["inner", "inner", "outer", "outer"]
    inner1, inner2, outer1, outer2 = ev
    assert len({e["id"] for e in ev}) == 4
    assert inner1["parent"] == inner2["parent"] == outer1["id"]
    assert outer1["parent"] is None and outer2["parent"] is None
    assert outer1["rid"] == 7 and outer1["n"] == 2
    assert outer1["start"] <= inner1["start"] <= inner1["end"] \
        <= inner2["start"] <= inner2["end"] <= outer1["end"]
    tot = t.totals["outer"]
    assert tot.count == 2 and t.totals["inner"].count == 2
    kids = t.totals["inner"].seconds
    assert tot.self_seconds == pytest.approx(tot.seconds - kids)
    assert t.span_seconds("outer") == tot.seconds
    assert t.span_seconds("never") == 0.0


def test_totals_counters_and_samples_outlive_the_ring():
    """A reader never depends on the ring's size: totals, counters and
    samples hold every span, count and sample however few events the ring
    keeps; ``configure`` starts them afresh."""
    t = trace.Tracer(ring_capacity=8)
    for i in range(50):
        with t.span("step", i=i):
            t.count("items", 3)
            t.observe("wait_s", i / 10, key=i)
    t.observe("wait_s", 9.0, key=49)          # a key's sample is replaced
    assert len(t.events) == 8
    assert t.totals["step"].count == 50
    assert t.counters["items"] == 150
    assert len(t.samples["wait_s"]) == 50 and t.samples["wait_s"][49] == 9.0
    g = trace.configure(None)
    with g.span("x"):
        g.count("c")
    g = trace.configure(None)
    assert not g.totals and not g.counters and not g.samples


def test_span_annotation_joins_its_record_by_id(tmp_path):
    """The profiler's host event of a span carries its id, parent and
    attributes, so the trace and the in-memory record join by id."""
    import glob

    from jax.profiler import ProfileData

    t = trace.Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with t.span("join.outer", rid=5) as attrs:
            with t.span("join.inner"):
                pass
            attrs["n"] = 3
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                           "*.xplane.pb"))
    found = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("join."):
                    found[ev.name] = dict(ev.stats)
    rec = {e["name"]: e for e in t.events}
    assert found["join.outer"] == {"id": rec["join.outer"]["id"], "rid": 5,
                                   "n": 3}
    assert found["join.inner"] == {"id": rec["join.inner"]["id"],
                                   "parent": rec["join.outer"]["id"]}


def test_registry_jsonl_matches_csv(tmp_path):
    reg = MetricsRegistry(str(tmp_path), sinks=("csv", "jsonl"))
    reg.record(10, {"loss": 0.5, "sps": 1000.0})
    reg.record(20, {"loss": 0.25, "sps": 1100.0})
    reg.close()
    with open(tmp_path / "progress.jsonl") as f:
        rows = [json.loads(line) for line in f]
    assert [r["step"] for r in rows] == [10, 20]
    with open(tmp_path / "progress.csv", newline="") as f:
        crows = list(csv.DictReader(f))
    assert [set(r) for r in rows] == [set(c) for c in crows]
    assert float(crows[1]["loss"]) == rows[1]["loss"] == 0.25


def test_csv_field_drift_and_restart_append(tmp_path):
    """The seed logger froze its header on the first record (later keys
    silently dropped) and misaligned columns on restart-append.  The CSV
    sink must instead grow the header in place and adopt it on restart."""
    log = lambda: Logger(str(tmp_path), stream=open(os.devnull, "w"),
                         sinks=("console", "csv"))
    l1 = log()
    l1.record(1, {"a": 1.0})
    l1.record(2, {"a": 2.0, "b": 20.0})        # field set GROWS mid-run
    l1.close()
    l2 = log()                                 # restart into existing file
    l2.record(3, {"a": 3.0, "b": 30.0, "c": 300.0})
    l2.close()
    with open(tmp_path / "progress.csv", newline="") as f:
        rows = list(csv.DictReader(f))
    assert list(rows[0]) == ["step", "wall_time", "a", "b", "c"]
    assert [r["a"] for r in rows] == ["1.0", "2.0", "3.0"]
    assert [r["b"] for r in rows] == ["", "20.0", "30.0"]
    assert [r["c"] for r in rows] == ["", "", "300.0"]


def test_tb_sink_writes_valid_tfevents(tmp_path):
    reg = MetricsRegistry(str(tmp_path), sinks=("tb",))
    reg.record(5, {"loss": 1.5})
    reg.close()
    files = [f for f in os.listdir(tmp_path) if f.startswith("events.out")]
    assert len(files) == 1
    with open(tmp_path / files[0], "rb") as f:
        data = f.read()
    # validate TFRecord framing of every record: len crc + payload crc
    off, n = 0, 0
    while off < len(data):
        header = data[off:off + 8]
        (length,) = struct.unpack("<Q", header)
        (len_crc,) = struct.unpack("<I", data[off + 8:off + 12])
        assert len_crc == _masked_crc(header)
        payload = data[off + 12:off + 12 + length]
        (pay_crc,) = struct.unpack("<I",
                                   data[off + 12 + length:off + 16 + length])
        assert pay_crc == _masked_crc(payload)
        off += 16 + length
        n += 1
    assert n == 2                               # file_version + one event
    assert b"brain.Event:2" in data and b"loss" in data


def test_kernel_dispatch_event(tmp_path):
    t = trace.configure(None)
    from repro.kernels import registry
    be = registry.backend_for("attention", site="unit_test")
    ev = [e for e in t.events
          if e["kind"] == "kernel_dispatch" and e.get("site") == "unit_test"]
    assert ev and ev[-1]["backend"] == be
    assert ev[-1]["name"] == "attention@unit_test"
