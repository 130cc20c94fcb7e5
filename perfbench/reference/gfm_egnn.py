"""Plain float32 reference of the GFM: EGNN trunk, per-source branches,
multi-task loss, gradients and AdamW, in straightforward ``jax.numpy``.

It follows the model as the paper describes it (arXiv 2506.21788 §5, the
HydraGNN EGNN) in the invariant form this repository trains: per layer an
edge MLP phi_e over [h_src, h_dst, |x_src - x_dst|^2] with SiLU between its
two dense layers, a masked sum of the messages into each edge's destination
atom, a node MLP phi_h over [h, aggregate], and a residual update masked to
real atoms. Each branch holds an energy MLP on the masked mean of the atom
features and a force MLP on each atom (``head_layers`` hidden layers of
``head_hidden``, SiLU). The loss of a source is the energy MSE plus the
force MSE over real atom components; the total is the weighted sum over
sources.

Nothing here imports the program. Every matrix product runs at
``Precision.HIGHEST``; ``compute`` lowers the dtype of the activations and
weights for a control run.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _dense(p, x, cd):
    y = jnp.dot(x.astype(cd), p["w"].astype(cd), precision=HIGHEST)
    return y + p["b"].astype(cd)


def mlp(p, x, cd):
    n = len(p)
    for i in range(n):
        x = _dense(p[f"fc{i}"], x, cd)
        if i < n - 1:
            x = jax.nn.silu(x)
    return x


def trunk(shared, batch, layers: int, cd):
    """Atom features (B, A, H) of a padded batch."""
    species, nm = batch["species"], batch["node_mask"]
    src, dst, em = batch["edge_src"], batch["edge_dst"], batch["edge_mask"]
    pos = batch["pos"].astype(jnp.float32)
    B, A = species.shape
    keep = nm[..., None].astype(cd)
    h = shared["embed"]["table"].astype(cd)[species] * keep
    s = jnp.minimum(src, A - 1)
    d = jnp.minimum(dst, A - 1)
    rows = jnp.arange(B)[:, None]
    d2 = jnp.sum((pos[rows, s] - pos[rows, d]) ** 2, -1, keepdims=True)
    # messages of pad edges go to one spare segment that is dropped
    seg = jnp.where(em, rows * A + d, B * A).reshape(-1)
    for i in range(layers):
        lp = shared[f"layer{i}"]
        msg = mlp(lp["phi_e"],
                  jnp.concatenate([h[rows, s], h[rows, d], d2.astype(cd)],
                                  -1), cd)
        msg = jnp.where(em[..., None], msg, 0)
        agg = jax.ops.segment_sum(msg.reshape(B * msg.shape[1], -1), seg,
                                  num_segments=B * A + 1)[:-1]
        agg = agg.reshape(B, A, -1).astype(cd)
        h = (h + mlp(lp["phi_h"], jnp.concatenate([h, agg], -1), cd)) * keep
    return h


def branch(bp, h, nm, cd):
    """-> per-atom energy (B,) and forces (B, A, 3), float32."""
    keep = nm[..., None].astype(cd)
    n = jnp.maximum(nm.sum(-1, keepdims=True), 1).astype(cd)
    pooled = (h * keep).sum(1) / n
    e = mlp(bp["energy"], pooled, cd)[..., 0]
    f = mlp(bp["force"], h, cd) * keep
    return e.astype(jnp.float32), f.astype(jnp.float32)


def forward(shared, bp, batch, layers: int, cd=jnp.float32):
    return branch(bp, trunk(shared, batch, layers, cd), batch["node_mask"],
                  cd)


def task_loss(shared, bp, batch, layers, cd):
    e, f = forward(shared, bp, batch, layers, cd)
    nm = batch["node_mask"]
    e_err = jnp.mean((e - batch["energy"]) ** 2)
    f_err = jnp.sum((f - batch["forces"]) ** 2 * nm[..., None]) \
        / jnp.maximum(nm.sum() * 3.0, 1.0)
    return e_err + f_err


def total_loss(params, batch, weights, layers, cd):
    """Weighted multi-task loss of a task-major batch (T, B, ...)."""
    losses = []
    for t in range(weights.shape[0]):
        bp = jax.tree_util.tree_map(lambda v: v[t], params["heads"])
        bt = {k: v[t] for k, v in batch.items()}
        losses.append(task_loss(params["shared"], bp, bt, layers, cd))
    losses = jnp.stack(losses)
    return jnp.sum(weights * losses), losses


def lr_at(step, hp):
    """Linear warm-up to ``lr`` over ``warmup`` steps, then cosine decay to
    zero at ``schedule_steps``; ``step`` counts from 1."""
    step = jnp.asarray(step, jnp.float32)
    peak, warm, total = hp["lr"], hp["warmup"], hp["schedule_steps"]
    if warm <= 0:
        return jnp.asarray(peak, jnp.float32)
    frac = jnp.clip((step - warm) / max(total - warm, 1), 0.0, 1.0)
    return jnp.where(step < warm, peak * step / warm,
                     0.5 * peak * (1.0 + jnp.cos(jnp.pi * frac)))


def adamw(params, grads, m, v, step, hp):
    """One AdamW update (bias-corrected moments, decoupled weight decay)."""
    b1, b2, eps, wd = 0.9, 0.999, 1e-8, hp["weight_decay"]
    lr = lr_at(step, hp)
    m = jax.tree_util.tree_map(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grads)
    v = jax.tree_util.tree_map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, v,
                               grads)
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step
    params = jax.tree_util.tree_map(
        lambda p, m_, v_: p - lr * ((m_ / c1) / (jnp.sqrt(v_ / c2) + eps)
                                    + wd * p), params, m, v)
    return params, m, v


def leaf_norms(tree) -> list:
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree_util.tree_leaves(tree)]


@functools.lru_cache(maxsize=None)
def _grad_fn(layers, cd):
    def f(params, batch, weights):
        (loss, per_task), g = jax.value_and_grad(
            lambda p: total_loss(p, batch, weights, layers, cd),
            has_aux=True)(params)
        return loss, per_task, g
    return jax.jit(f)


@functools.partial(jax.jit, static_argnums=(5,))
def _update(params, grads, m, v, step, hp_items):
    return adamw(params, grads, m, v, step, dict(hp_items))


def train_readings(params, batches, weights, hp, layers: int,
                   cd=jnp.float32) -> dict:
    """Follow the first ``len(batches)`` training steps from ``params``.

    -> ``loss``: the loss of each step; ``grad_norms``: per-leaf norms of
    the first step's gradient; ``change_norms``: per-leaf norms of the
    parameters' change over all the steps (the parameters the next step
    would receive, less ``params``)."""
    hp_items = tuple(sorted((k, float(v)) for k, v in hp.items()))
    w = jnp.asarray(weights, jnp.float32)
    p = params
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, grad_norms = [], None
    with jax.default_matmul_precision("highest"):
        for i, b in enumerate(batches):
            loss, _, g = _grad_fn(layers, cd)(p, b, w)
            losses.append(float(loss))
            if grad_norms is None:
                grad_norms = [float(x) for x in leaf_norms(g)]
            p, m, v = _update(p, g, m, v, jnp.float32(i + 1), hp_items)
        change = jax.tree_util.tree_map(lambda a, b: a - b, p, params)
        change_norms = [float(x) for x in leaf_norms(change)]
    return {"loss": losses, "grad_norms": grad_norms,
            "change_norms": change_norms}


@functools.lru_cache(maxsize=None)
def _serve_fn(layers, cd):
    def f(shared, heads, batch, head_idx):
        h = trunk(shared, batch, layers, cd)
        n_heads = jax.tree_util.tree_leaves(heads)[0].shape[0]
        outs = [branch(jax.tree_util.tree_map(lambda v: v[t], heads), h,
                       batch["node_mask"], cd) for t in range(n_heads)]
        e = jnp.stack([o[0] for o in outs])          # (T, N)
        f = jnp.stack([o[1] for o in outs])          # (T, N, A, 3)
        rows = jnp.arange(head_idx.shape[0])
        return e[head_idx, rows], f[head_idx, rows]
    return jax.jit(f)


def serve_readings(params, samples, heads, layers: int, cd=jnp.float32):
    """Energy (N,) and forces (N, A, 3) of single structures, each through
    its own head. ``samples``: dict of (N, ...) arrays, one structure per
    row, padded to one common (A, E)."""
    batch = {k: jnp.asarray(v) for k, v in samples.items()}
    with jax.default_matmul_precision("highest"):
        e, f = _serve_fn(layers, cd)(params["shared"], params["heads"],
                                     batch, jnp.asarray(np.asarray(heads)))
    return np.asarray(e), np.asarray(f)
