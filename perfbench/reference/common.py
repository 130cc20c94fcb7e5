"""What every architecture's plain reference shares: dense layers, the
per-source energy-plus-force loss, AdamW, and the two readings the runners
compare with the program.

An architecture's reference module (the file a configuration's
``reference`` key names) brings what is its own; the runners pass its
``trunk`` and ``branch`` to the readings here:

  * ``init(cfg, key)``: the parameters in the program's layout,
    ``{"shared": trunk, "heads": branches stacked on a leading head axis}``;
  * ``trunk(shared, batch, cfg, cd)``: the features of a padded batch;
  * ``branch(bp, features, batch, cd)``: one branch on them, giving energy
    (B,) and forces (B, A, 3) in float32; trunk then branch is the forward;
  * ``forward_flops(cfg, *, structures, atoms, edges)``: the forward's
    operations for the real structures, atoms and edges;
  * ``tiny(cfg)``: the configuration at the small sizes of the CPU tests.

Nothing here imports the program. Every matrix product runs at
``Precision.HIGHEST``; ``cd`` lowers the dtype of the activations and
weights for a control run.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def mlp_shapes(d_in: int, hidden: int, d_out: int, n_hidden: int):
    dims = [d_in] + [hidden] * n_hidden + [d_out]
    return [(dims[i], dims[i + 1]) for i in range(len(dims) - 1)]


def mlp_macs(d_in: int, hidden: int, d_out: int, n_hidden: int) -> int:
    """Multiply-adds of one row through d_in -> hidden (x n_hidden) ->
    d_out."""
    return sum(a * b for a, b in mlp_shapes(d_in, hidden, d_out, n_hidden))


def mlp_init(key, shapes, lead, dtype):
    """Dense layers ``fc0``, ``fc1``, ... of ``shapes``, each with a leading
    ``lead`` axis; weights N(0, 1/fan_in), biases N(0, 1e-4)."""
    out = {}
    for i, (a, b) in enumerate(shapes):
        kw, kb = jax.random.split(jax.random.fold_in(key, i))
        out[f"fc{i}"] = {
            "w": (jax.random.normal(kw, lead + (a, b), jnp.float32)
                  / np.sqrt(a)).astype(dtype),
            "b": (0.01 * jax.random.normal(kb, lead + (b,), jnp.float32)
                  ).astype(dtype)}
    return out


def _dense(p, x, cd):
    y = jnp.dot(x.astype(cd), p["w"].astype(cd), precision=HIGHEST)
    return y + p["b"].astype(cd)


def mlp(p, x, cd):
    """The dense layers of ``p`` in order, SiLU between them."""
    n = len(p)
    for i in range(n):
        x = _dense(p[f"fc{i}"], x, cd)
        if i < n - 1:
            x = jax.nn.silu(x)
    return x


def task_loss(trunk, branch, shared, bp, batch, cfg, cd):
    """One source's energy MSE plus its force MSE over real atom
    components."""
    e, f = branch(bp, trunk(shared, batch, cfg, cd), batch, cd)
    nm = batch["node_mask"]
    e_err = jnp.mean((e - batch["energy"]) ** 2)
    f_err = jnp.sum((f - batch["forces"]) ** 2 * nm[..., None]) \
        / jnp.maximum(nm.sum() * 3.0, 1.0)
    return e_err + f_err


def total_loss(trunk, branch, params, batch, weights, cfg, cd):
    """Weighted multi-task loss of a task-major batch (T, B, ...)."""
    losses = []
    for t in range(weights.shape[0]):
        bp = jax.tree_util.tree_map(lambda v: v[t], params["heads"])
        bt = {k: v[t] for k, v in batch.items()}
        losses.append(task_loss(trunk, branch, params["shared"], bp, bt, cfg,
                                cd))
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


def _frozen(cfg: dict) -> str:
    """A configuration as a hashable key of the compiled readings."""
    return json.dumps(cfg, sort_keys=True)


@functools.lru_cache(maxsize=None)
def _grad_fn(trunk, branch, cfg_key, cd):
    cfg = json.loads(cfg_key)

    def f(params, batch, weights):
        (loss, per_task), g = jax.value_and_grad(
            lambda p: total_loss(trunk, branch, p, batch, weights, cfg, cd),
            has_aux=True)(params)
        return loss, per_task, g
    return jax.jit(f)


@functools.partial(jax.jit, static_argnums=(5,))
def _update(params, grads, m, v, step, hp_items):
    return adamw(params, grads, m, v, step, dict(hp_items))


def train_readings(trunk, branch, params, batches, weights, hp, cfg: dict,
                   cd=jnp.float32) -> dict:
    """Follow the first ``len(batches)`` training steps from ``params``.

    -> ``loss``: the loss of each step; ``grad_norms``: per-leaf norms of
    the first step's gradient; ``change_norms``: per-leaf norms of the
    parameters' change over all the steps (the parameters the next step
    would receive, less ``params``)."""
    hp_items = tuple(sorted((k, float(v)) for k, v in hp.items()))
    w = jnp.asarray(weights, jnp.float32)
    grad_fn = _grad_fn(trunk, branch, _frozen(cfg), cd)
    p = params
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, grad_norms = [], None
    with jax.default_matmul_precision("highest"):
        for i, b in enumerate(batches):
            loss, _, g = grad_fn(p, b, w)
            losses.append(float(loss))
            if grad_norms is None:
                grad_norms = [float(x) for x in leaf_norms(g)]
            p, m, v = _update(p, g, m, v, jnp.float32(i + 1), hp_items)
        change = jax.tree_util.tree_map(lambda a, b: a - b, p, params)
        change_norms = [float(x) for x in leaf_norms(change)]
    return {"loss": losses, "grad_norms": grad_norms,
            "change_norms": change_norms}


@functools.lru_cache(maxsize=None)
def _serve_fn(trunk, branch, cfg_key, cd):
    cfg = json.loads(cfg_key)

    def f(shared, heads, batch, head_idx):
        h = trunk(shared, batch, cfg, cd)
        n_heads = jax.tree_util.tree_leaves(heads)[0].shape[0]
        outs = [branch(jax.tree_util.tree_map(lambda v: v[t], heads), h,
                       batch, cd) for t in range(n_heads)]
        e = jnp.stack([o[0] for o in outs])          # (T, N)
        f = jnp.stack([o[1] for o in outs])          # (T, N, A, 3)
        rows = jnp.arange(head_idx.shape[0])
        return e[head_idx, rows], f[head_idx, rows]
    return jax.jit(f)


def serve_readings(trunk, branch, params, samples, heads, cfg: dict,
                   cd=jnp.float32):
    """Energy (N,) and forces (N, A, 3) of single structures, each through
    its own head. ``samples``: dict of (N, ...) arrays, one structure per
    row, padded to one common (A, E)."""
    batch = {k: jnp.asarray(v) for k, v in samples.items()}
    with jax.default_matmul_precision("highest"):
        e, f = _serve_fn(trunk, branch, _frozen(cfg), cd)(
            params["shared"], params["heads"], batch,
            jnp.asarray(np.asarray(heads)))
    return np.asarray(e), np.asarray(f)
