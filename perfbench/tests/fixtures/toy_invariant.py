"""A toy architecture of two leaves, for the tests of the seam between the
yardstick and an architecture's reference module (``reference/common.py``).

Invariant: each atom's feature is its species' row of ``embed`` (width
``width``); a branch maps the mean feature of a structure to its energy and
each atom's feature to its force through one (width, 4) matrix per head.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.reference import common


def init(cfg: dict, key):
    k_embed, k_out = jax.random.split(key)
    width, dtype = cfg["width"], jnp.dtype(cfg["param_dtype"])
    embed = jax.random.normal(k_embed, (cfg["n_species"], width), jnp.float32)
    out = jax.random.normal(k_out, (cfg["n_tasks"], width, 4), jnp.float32)
    return {"shared": {"embed": embed.astype(dtype)},
            "heads": {"out": (out / width ** 0.5).astype(dtype)}}


def tiny(cfg: dict) -> dict:
    return dict(cfg, width=4)


def forward_flops(cfg: dict, *, structures: int, atoms: int,
                  edges: int) -> int:
    """The energy column once per structure, the three force columns once
    per atom; the trunk is a lookup."""
    return 2 * cfg["width"] * (structures + 3 * atoms)


def trunk(shared, batch, cfg: dict, cd):
    keep = batch["node_mask"][..., None].astype(cd)
    return shared["embed"].astype(cd)[batch["species"]] * keep


def branch(bp, h, batch, cd):
    nm = batch["node_mask"]
    w = bp["out"].astype(cd)
    pooled = h.sum(1) / jnp.maximum(nm.sum(-1, keepdims=True), 1).astype(cd)
    e = jnp.dot(pooled, w[:, 0], precision=common.HIGHEST)
    f = jnp.dot(h, w[:, 1:], precision=common.HIGHEST) \
        * nm[..., None].astype(cd)
    return e.astype(jnp.float32), f.astype(jnp.float32)

