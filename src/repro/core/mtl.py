"""Two-level hierarchical MTL models (paper §4.2), as MultiTaskModel bundles.

Level 1: one branch per data source. Level 2: each branch = {energy head,
force head}. Three model variants reproduce the paper's Tables 1–2 setup:

  * ``make_gfm_mtl``       — GFM-MTL-All: shared EGNN + per-source branches
  * ``make_gfm_baseline``  — GFM-Baseline-All: shared EGNN + ONE branch for
                              all sources (n_tasks=1 over mixed data)
  * single-source models are just ``make_gfm_mtl`` with n_tasks=1 on one
    source's data.

Also ``make_lm_multitask`` — the paper's technique carried onto the assigned
LLM architectures: shared transformer trunk + per-source LM heads.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.models import gnn, heads, transformer
from repro.models.common import KeyGen
from .taskpar import MultiTaskModel


# ---------------------------------------------------------------------------
# GFM (HydraGNN): EGNN trunk + stacked {energy, force} branches
# ---------------------------------------------------------------------------

def gfm_loss_terms(e_pred, f_pred, batch_t, force_weight=1.0):
    """Masked MSE on energy-per-atom + forces for one task's sub-batch.
    Its device ops carry the named scope ``loss``."""
    with jax.named_scope("loss"):
        nm = batch_t["node_mask"]
        e_err = jnp.mean(jnp.square(e_pred - batch_t["energy"]))
        f_err = jnp.sum(jnp.square(f_pred - batch_t["forces"])
                        * nm[..., None]) / jnp.maximum(jnp.sum(nm) * 3.0, 1.0)
        return e_err + force_weight * f_err, e_err, f_err


def make_gfm_mtl(cfg, n_tasks: int, force_weight: float = 1.0,
                 uncertainty: bool = False) -> MultiTaskModel:
    """uncertainty=True adds Kendall homoscedastic weighting: each branch
    owns learnable log sigma^2 for its (energy, force) pair — the weights
    live with the branch, so they shard over the task axis like any other
    head parameter."""
    def init(key):
        kg = KeyGen(key)
        hp = heads.stacked_branches_init(kg(), cfg, n_tasks)
        if uncertainty:
            hp["log_sigma2"] = jnp.zeros((n_tasks, 2), jnp.float32)
        return {"shared": gnn.egnn_init(kg(), cfg), "heads": hp}

    def loss_fn(shared, hp, batch):
        # batch leaves are task-major: (T, B, ...)
        def per_task(hp_t, batch_t):
            feats = gnn.egnn_apply(shared, batch_t, cfg=cfg)
            e, f = heads.branch_apply(
                {k: v for k, v in hp_t.items() if k != "log_sigma2"},
                feats, batch_t["node_mask"], cfg=cfg)
            _, e_err, f_err = gfm_loss_terms(e, f, batch_t, force_weight)
            if uncertainty:
                s = hp_t["log_sigma2"]
                l = (jnp.exp(-s[0]) * e_err + s[0]
                     + jnp.exp(-s[1]) * force_weight * f_err + s[1])
            else:
                l = e_err + force_weight * f_err
            return l, (e_err, f_err)

        ls, (e_errs, f_errs) = jax.vmap(per_task)(hp, batch)
        return ls, {"energy_mse": e_errs, "force_mse": f_errs}

    return MultiTaskModel(init=init, loss_fn=loss_fn,
                          name=f"gfm-mtl-{n_tasks}", n_tasks=n_tasks)


def gfm_eval_fn(cfg):
    """Returns eval(shared, head_t, batch_single_task) -> (energy MAE, force MAE)."""
    def ev(shared, hp_t, batch_t):
        feats = gnn.egnn_apply(shared, batch_t, cfg=cfg)
        e, f = heads.branch_apply(hp_t, feats, batch_t["node_mask"], cfg=cfg)
        nm = batch_t["node_mask"]
        e_mae = jnp.mean(jnp.abs(e - batch_t["energy"]))
        f_mae = jnp.sum(jnp.abs(f - batch_t["forces"]) * nm[..., None]) / \
            jnp.maximum(jnp.sum(nm) * 3.0, 1.0)
        return e_mae, f_mae
    return jax.jit(ev)


# ---------------------------------------------------------------------------
# LM multi-task: shared transformer trunk + per-source vocab heads
# ---------------------------------------------------------------------------

def softmax_xent(logits, labels):
    """logits: (..., V) f32; labels: (...) int. Mean over all positions."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def make_lm_multitask(cfg, impl="chunked") -> MultiTaskModel:
    assert cfg.n_tasks > 1

    def init(key):
        kg = KeyGen(key)
        p = transformer.lm_init(kg(), cfg)
        hp = {"w": p.pop("task_heads")["w"]}
        return {"shared": p, "heads": hp}

    def loss_fn(shared, hp, batch):
        # batch: {"tokens": (T,B,S), "labels": (T,B,S)}
        def per_task(hw, toks, labels):
            x = transformer.embed_inputs(shared, toks, cfg)
            h, _, aux = transformer.run_trunk(
                shared, x, cfg=cfg, positions=jnp.arange(toks.shape[-1]),
                mode="train", impl=impl)
            logits = jnp.einsum("bsd,dv->bsv", h, hw.astype(h.dtype),
                                preferred_element_type=jnp.float32)
            return softmax_xent(logits, labels) + cfg.router_aux_coef * aux

        ls = jax.vmap(per_task)(hp["w"], batch["tokens"], batch["labels"])
        return ls, {}

    return MultiTaskModel(init=init, loss_fn=loss_fn,
                          name=f"lm-mtl-{cfg.name}", n_tasks=cfg.n_tasks)
