"""Production meshes. Functions, not module constants — importing this module
never touches jax device state."""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh


def _make_mesh(shape, axes) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto`` (sharding propagated by
    the compiler, as pjit expects)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_alt_mesh(model: int = 8) -> Mesh:
    """Same 256-chip pod, reshaped so the TP degree divides awkward head
    counts (e.g. granite's 24 heads on model=8) — §Perf-2 mesh-reshape."""
    return _make_mesh((256 // model, model), ("data", "model"))


def make_gfm_paper_mesh(n_tasks: int = 5, dp: int = 100) -> Mesh:
    """The paper's process layout: N=5 head sub-groups x M data-parallel
    ranks (paper: 640 GPUs = 5 x 128 on Frontier; here 5 x 100 of the 512
    placeholder devices)."""
    devs = np.array(jax.devices()[: dp * n_tasks]).reshape(dp, n_tasks)
    return Mesh(devs, ("data", "model"))


def make_host_mesh(data: int, model: int) -> Mesh:
    """Small mesh over however many host devices exist (tests/examples)."""
    return _make_mesh((data, model), ("data", "model"))


def make_group_meshes(placement, *, devices=None) -> list[Mesh]:
    """Per-group sub-meshes for a hierarchical plan: the device pool is
    partitioned contiguously by ``placement.device_counts`` and each slice
    becomes a 1-axis ``("data",)`` mesh — within a group the batch is
    data-parallel and the group's head slice is replicated, so the group IS
    its heads' model shard (the paper's head sub-group).

    devices: explicit device list (length >= placement.n_devices); defaults
    to ``jax.devices()``. Raises if the pool is too small."""
    devs = list(devices) if devices is not None else jax.devices()
    need = placement.n_devices
    assert len(devs) >= need, (
        f"placement needs {need} devices, host has {len(devs)} — solve the "
        f"placement against the real device count")
    meshes, off = [], 0
    for c in placement.device_counts:
        meshes.append(Mesh(np.array(devs[off: off + c]), ("data",)))
        off += c
    return meshes


def make_replica_meshes(n_replicas: int, *, devices_per_replica: int = 1,
                        devices=None) -> list[Mesh]:
    """Serving scale-out meshes: partition the device pool into
    ``n_replicas`` disjoint 1-axis ``("data",)`` sub-meshes of
    ``devices_per_replica`` each. Built on the SAME ``make_group_meshes``
    machinery as training's hierarchical plan — each serving replica is a
    degenerate head group that owns EVERY head (replicated params, rows
    data-parallel within the replica), so ``ServeSession(mesh=...)`` /
    ``ReplicaServeSession`` reuse the training mesh contract unchanged."""
    from repro.core.taskpar import HeadPlacement
    assert n_replicas >= 1 and devices_per_replica >= 1
    placement = HeadPlacement(
        groups=tuple((g,) for g in range(n_replicas)),
        device_counts=(devices_per_replica,) * n_replicas)
    return make_group_meshes(placement, devices=devices)
