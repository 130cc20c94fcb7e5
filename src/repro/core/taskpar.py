"""Multi-task parallelism (the paper's contribution), in JAX SPMD.

The paper (§4.3–4.4) distributes the per-dataset MTL decoding heads across
process sub-groups: every process holds the shared trunk plus exactly ONE
head; head gradients all-reduce only inside the head's sub-group (local DDP)
while trunk gradients all-reduce globally. Memory per device falls from
``P_s + N_h·P_h`` to ``P_s + P_h``.

JAX mapping — the mesh's ``model`` axis doubles as the **task axis**:

  * heads are stacked ``(n_tasks, …)`` arrays; dim 0 sharded over ``model``
    (mode="par") or replicated (mode="base", the paper's MTL-base baseline);
  * the batch is task-major ``(n_tasks, per_task_batch, …)``: dim 0 follows
    the heads' sharding, dim 1 shards over the data axes;
  * trunk params replicated (or FSDP/TP-sharded via ``shared_spec_fn``).

With those shardings, XLA's SPMD partitioner emits exactly the paper's two
collective scopes for the backward pass: a global all-reduce for trunk grads
and a sub-group (data-axes-only) reduce for head grads. A ``shard_map``
variant makes the two ``psum`` scopes explicit and is used to cross-validate
the pjit path (tests/test_taskpar.py).

This module owns the *sharding vocabulary* only: ``MTPConfig``, the
``MultiTaskModel`` contract, the param/batch sharding builders and the
explicit-collective ``mtp_value_and_grad_shardmap``. Train-step construction
and compilation live in ``repro.engine``: build a step with
``engine.make_step(model, optimizer, plan)`` and compile it with
``ShardingPlan(mesh=..., mtp=..., backend=...).compile(step)`` — the single
public path covering single-device jit, the pjit sharding formulation
(mode="par"/"base") and the shard_map backend behind one signature.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Params = Any


@dataclasses.dataclass(frozen=True)
class MTPConfig:
    n_tasks: int
    mode: str = "par"              # "par" (task-sharded heads) | "base" (replicated)
    task_axis: str = "model"
    data_axes: tuple = ("data",)   # may include "pod"

    @property
    def all_axes(self) -> tuple:
        return tuple(self.data_axes) + (self.task_axis,)


class MultiTaskModel(NamedTuple):
    """init -> {"shared": ..., "heads": stacked-leading-task-dim}.
    loss_fn(shared, heads, batch) -> (per_task_loss: (n_tasks,), metrics).
    n_tasks: number of heads/branches (0 = unknown, for hand-built bundles;
    the repo's builders always set it — Session uses it to pair data sources
    with heads)."""
    init: Callable
    loss_fn: Callable
    name: str = "mtl"
    n_tasks: int = 0


# ---------------------------------------------------------------------------
# Sharding builders
# ---------------------------------------------------------------------------

def head_pspec(mtp: MTPConfig, leaf_ndim: int) -> P:
    if mtp.mode == "par":
        return P(mtp.task_axis, *([None] * (leaf_ndim - 1)))
    return P(*([None] * leaf_ndim))


def param_shardings(mesh: Mesh, params: Params, mtp: MTPConfig,
                    shared_spec_fn: Callable | None = None):
    """NamedSharding tree for {"shared", "heads"} params."""
    def shared_spec(path, leaf):
        if shared_spec_fn is not None:
            return shared_spec_fn(path, leaf)
        return P()

    def build(tree, fn):
        flat = jax.tree_util.tree_flatten_with_path(tree)
        specs = [fn(p, l) for p, l in flat[0]]
        return jax.tree_util.tree_unflatten(flat[1], [
            NamedSharding(mesh, s) for s in specs])

    out = {}
    out["shared"] = build(params["shared"], shared_spec)
    out["heads"] = build(params["heads"], lambda p, l: head_pspec(mtp, l.ndim))
    return out


def batch_shardings(mesh: Mesh, batch: Params, mtp: MTPConfig):
    """Task-major batch (n_tasks, B, ...). par: tasks over task_axis, B over
    data axes. base: tasks replicated, B over ALL axes (pure DDP). Leaves
    with fewer than 2 dims (e.g. stacked per-task weights (n_tasks,)) get
    the spec truncated to their rank."""
    def spec(leaf):
        nd = leaf.ndim
        if mtp.mode == "par":
            entries = (mtp.task_axis, tuple(mtp.data_axes))
        else:
            entries = (None, mtp.all_axes)
        s = P(*(entries[:nd] + tuple([None] * (nd - 2))))
        return NamedSharding(mesh, s)

    return jax.tree_util.tree_map(spec, batch)


def memory_per_device(p_shared: int, p_head: int, n_heads: int, mode: str) -> int:
    """Paper §4.3: parameter count resident per device."""
    return p_shared + (p_head if mode == "par" else n_heads * p_head)


# ---------------------------------------------------------------------------
# Hierarchical placement vocabulary (data-parallel replicas x per-head
# model shards): heads -> device groups, possibly UNEVEN — the Exascale
# follow-up's point is that imbalanced multi-fidelity batch mixes make
# uneven head-to-device assignment the thing that matters at scale.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class HeadPlacement:
    """Head -> device-group assignment for the hierarchical backend.

    ``groups[g]`` is the tuple of head indices owned by group g;
    ``device_counts[g]`` is how many devices group g gets. Groups partition
    BOTH the heads (every head in exactly one group) and the device pool
    (counts sum to ``n_devices``). Within a group the batch is data-parallel
    over the group's devices and the group's head slice is resident only
    there — memory per device is ``P_s + Σ_{t∈g} P_h(t)``, the paper's
    §4.3 number when groups hold one head each.

    ``loads`` optionally records the per-head load model the placement was
    solved against (``repro.data.mixing`` weights); it is bookkeeping only.
    """
    groups: tuple                  # ((head, ...), ...) — disjoint, exhaustive
    device_counts: tuple           # devices per group, all >= 1
    loads: tuple | None = None     # per-head load model used by the solver

    def __post_init__(self):
        groups = tuple(tuple(int(h) for h in g) for g in self.groups)
        counts = tuple(int(c) for c in self.device_counts)
        object.__setattr__(self, "groups", groups)
        object.__setattr__(self, "device_counts", counts)
        assert len(groups) == len(counts), \
            f"{len(groups)} groups vs {len(counts)} device counts"
        assert all(c >= 1 for c in counts), f"empty device group: {counts}"
        assert all(len(g) >= 1 for g in groups), f"headless group: {groups}"
        flat = [h for g in groups for h in g]
        assert sorted(flat) == list(range(len(flat))), \
            f"groups must partition heads 0..{len(flat) - 1}, got {groups}"
        if self.loads is not None:
            loads = tuple(float(x) for x in self.loads)
            object.__setattr__(self, "loads", loads)
            assert len(loads) == len(flat), \
                f"{len(loads)} loads for {len(flat)} heads"

    @property
    def n_heads(self) -> int:
        return sum(len(g) for g in self.groups)

    @property
    def n_groups(self) -> int:
        return len(self.groups)

    @property
    def n_devices(self) -> int:
        return sum(self.device_counts)

    def group_of(self, head: int) -> int:
        for g, heads in enumerate(self.groups):
            if head in heads:
                return g
        raise KeyError(head)

    def group_loads(self, loads=None) -> tuple:
        """Modeled per-DEVICE load of each group: Σ_{t∈g} load_t / n_g.
        ``loads`` defaults to the solver's recorded load model (uniform if
        none was recorded)."""
        w = self.loads if loads is None else tuple(float(x) for x in loads)
        if w is None:
            w = (1.0,) * self.n_heads
        assert len(w) == self.n_heads, f"{len(w)} loads for {self.n_heads} heads"
        return tuple(sum(w[t] for t in g) / c
                     for g, c in zip(self.groups, self.device_counts))

    def max_group_load(self, loads=None) -> float:
        """The placement's modeled bottleneck: max per-device group load —
        the quantity the solver minimizes and the step-time model on real
        (non-oversubscribed) hardware."""
        return max(self.group_loads(loads))


def round_robin_placement(n_heads: int, n_devices: int) -> HeadPlacement:
    """The load-blind baseline: heads dealt cyclically over
    ``min(n_heads, n_devices)`` groups, devices dealt cyclically over the
    same groups — even-as-possible sizes, no regard for per-head load."""
    assert n_heads >= 1 and n_devices >= 1
    n_groups = min(n_heads, n_devices)
    groups = [[] for _ in range(n_groups)]
    for t in range(n_heads):
        groups[t % n_groups].append(t)
    counts = [n_devices // n_groups + (1 if g < n_devices % n_groups else 0)
              for g in range(n_groups)]
    return HeadPlacement(groups=tuple(tuple(g) for g in groups),
                         device_counts=tuple(counts))


# ---------------------------------------------------------------------------
# shard_map explicit-collective formulation (paper-verbatim psum scopes)
# ---------------------------------------------------------------------------

def mtp_value_and_grad_shardmap(model: MultiTaskModel, mesh: Mesh,
                                mtp: MTPConfig):
    """Explicit two-scope gradient sync. Requires n_tasks == task-axis size.
    Returns f(params, batch) -> (loss, per_task_loss, grads) numerically
    identical to the pjit path (head grads carry the 1/n_tasks factor of the
    mean-over-tasks loss); per_task_loss is (n_tasks,), each entry averaged
    over that task's data sub-group."""
    ax_t = mtp.task_axis
    ax_d = tuple(mtp.data_axes)
    n_t = mtp.n_tasks
    assert mesh.shape[ax_t] == n_t, (
        f"shard_map path needs n_tasks == mesh['{ax_t}'] "
        f"({n_t} vs {mesh.shape[ax_t]})")

    def local(shared, heads_local, batch_local):
        # heads_local / batch_local have a leading task dim of size 1
        def loss(sh, hd):
            per_task, _ = model.loss_fn(sh, hd, batch_local)
            return per_task[0]

        l, (gs, gh) = jax.value_and_grad(loss, argnums=(0, 1))(
            shared, heads_local)
        # paper: trunk grads -> global group; head grads -> sub-group only.
        # The global pmean includes the 1/n_tasks of the mean-over-tasks loss;
        # head grads live in a single sub-group, so they carry it explicitly.
        gs = jax.lax.pmean(gs, ax_d + (ax_t,))
        gh = jax.lax.pmean(gh, ax_d)
        gh = jax.tree_util.tree_map(lambda g: g / n_t, gh)
        l_task = jax.lax.pmean(l, ax_d)              # this task's loss
        per_task = jax.lax.all_gather(l_task, ax_t)  # (n_tasks,), replicated
        l = jax.lax.pmean(l_task, ax_t)
        return l, per_task, gs, gh

    def shead(leaf_ndim):
        return P(ax_t, *([None] * (leaf_ndim - 1)))

    def f(params, batch):
        shared, heads = params["shared"], params["heads"]
        in_specs = (
            jax.tree_util.tree_map(lambda l: P(), shared),
            jax.tree_util.tree_map(lambda l: shead(l.ndim), heads),
            jax.tree_util.tree_map(
                lambda l: P(ax_t, ax_d, *([None] * (l.ndim - 2))), batch),
        )
        out_specs = (
            P(),
            P(),
            jax.tree_util.tree_map(lambda l: P(), shared),
            jax.tree_util.tree_map(lambda l: shead(l.ndim), heads),
        )
        fn = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
        l, per_task, gs, gh = fn(shared, heads, batch)
        return l, per_task, {"shared": gs, "heads": gh}

    return f
