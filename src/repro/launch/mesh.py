"""Production mesh definitions.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state; the dry-run sets XLA_FLAGS before calling.

Mesh shapes (TPU v5e pod = 16x16 = 256 chips):
- single-pod: (16, 16) over ('data', 'model')
- multi-pod:  (2, 16, 16) over ('pod', 'data', 'model') — 512 chips; the
  'pod' axis is outer data parallelism whose all-reduce crosses pod links
  (the int8-EF-compression target).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from ..models import sharding as shd


def auto_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: the code relies on GSPMD
    propagation and ``with_sharding_constraint`` (models/sharding.py), and
    on shard_map bodies that reshape and gather freely — both of which the
    ``Explicit`` axes ``jax.make_mesh`` now defaults to reject."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_test_mesh(n_data: int = 2, n_model: int = 2):
    """Small mesh for CPU tests (requires >=4 forced host devices)."""
    return auto_mesh((n_data, n_model), ("data", "model"))


def make_data_mesh(n_data: int = 0, axis: str = "data"):
    """1-D data-parallel mesh for SPMD RL training (paper §2.4: replicated
    model, sharded envs/replay, all-reduced gradients).  This is the mesh
    ShardedSampler + TrainLoop(mesh=...) expect; n_data=0 uses every local
    device.  RL models are small, so there is no 'model' axis — scaling is
    pure data parallelism, unlike the LM meshes above."""
    n = n_data or jax.local_device_count()
    return auto_mesh((n,), (axis,))


def make_2d_mesh(n_data: int = 0, n_model: int = 1,
                 axes=("data", "model")):
    """(data x model) mesh for model-parallel LM-scale PPO.

    The 'data' axis is the gradient all-reduce axis (manual inside the
    shard_map'd train step, so the reduction can route through the int8
    error-feedback compressor); the 'model' axis shards LM backbone
    params/activations through models/sharding.py rules (GSPMD 'auto' axis).
    ``n_data=0`` infers the data extent from the local device count.
    """
    if n_model < 1:
        raise ValueError(f"n_model must be >= 1, got {n_model}")
    avail = jax.local_device_count()
    n_data = n_data or max(avail // n_model, 1)
    if n_data * n_model > avail:
        raise ValueError(
            f"mesh {n_data}x{n_model} needs {n_data * n_model} devices, "
            f"host has {avail} (set XLA_FLAGS="
            f"--xla_force_host_platform_device_count=N for CPU tests)")
    return auto_mesh((n_data, n_model), axes)


def parse_mesh_arg(spec: str):
    """'DxM' (e.g. '2x2', '1x4') -> (n_data, n_model); '1x1'/'' -> None."""
    if not spec:
        return None
    parts = spec.lower().replace(",", "x").split("x")
    if len(parts) != 2:
        raise ValueError(f"mesh spec must be DATAxMODEL, got {spec!r}")
    n_data, n_model = int(parts[0]), int(parts[1])
    if n_data == n_model == 1:
        return None
    return n_data, n_model


def mesh_devices(mesh) -> set:
    """The device ids a mesh owns."""
    return {d.id for d in mesh.devices.flat}


def split_actor_learner(devices=None, *, mesh=None):
    """Disjoint device sets for the decoupled async runner (paper §2.3).

    Returns ``(actor_device, learner_device)``.  On a multi-device host the
    learner pins to device 0 and the actor to the LAST device, so the two
    compiled programs (rollout and update) never contend for a compute
    stream; remaining devices stay free for a future sharded learner.  On a
    single-device host both share device 0 — the runner then relies on
    donated update buffers plus async dispatch to interleave the streams.

    ``mesh``: a data/learner mesh that already owns devices (e.g. from
    ``make_data_mesh``).  Actor and learner then pick from the devices the
    mesh does NOT own, so the async programs never contend with the mesh'd
    program for a compute stream.  Raises when the mesh owns every device —
    sharing a shard_map'd device silently serializes both programs, which is
    worse than failing loudly.
    """
    devs = list(devices) if devices is not None else list(jax.local_devices())
    if mesh is not None:
        owned = mesh_devices(mesh)
        devs = [d for d in devs if d.id not in owned]
        if not devs:
            raise ValueError(
                f"mesh owns all devices ({sorted(owned)}); shrink the mesh "
                f"(make_data_mesh(n) with n < device count) to leave actor/"
                f"learner devices free")
    if not devs:
        raise ValueError("no devices available")
    if len(devs) == 1:
        return devs[0], devs[0]
    return devs[-1], devs[0]


def install(mesh):
    """Register mesh with the sharding-rule module (dp/tp axis names)."""
    if mesh is None:
        shd.set_global_mesh(None)
        return None
    axes = mesh.axis_names
    dp = tuple(a for a in axes if a != "model")
    shd.set_global_mesh(mesh, dp_axes=dp, tp_axis="model")
    return mesh


def install_2d(mesh):
    """Register a (data x model) mesh for the shard_map'd train path.

    Unlike :func:`install`, the data axes are NOT registered as dp axes:
    inside ``jax.shard_map(..., axis_names={'data'})`` the batch dims are
    shard-local (manual over 'data'), and a sharding constraint naming a
    manual axis is
    an error — only the auto 'model' axis may appear in constraints.  Batch
    specs therefore resolve to unsharded dims while param/activation rules
    keep their model-axis sharding.
    """
    if mesh is None:
        shd.set_global_mesh(None)
        return None
    shd.set_global_mesh(mesh, dp_axes=(), tp_axis="model")
    return mesh

