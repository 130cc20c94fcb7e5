"""Weights of the benchmark's GFM, made on the device from ``--seed``.

One jitted call draws every leaf in the layout the system under test takes
(``{"shared": EGNN trunk, "heads": branches stacked on a leading head
axis}``). The reference draws the same leaves from the same seed with the
same call, so it never takes weights the program has touched.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def seed_words(seed: int, stream: int) -> np.ndarray:
    """Two uint32 words for stream ``stream`` of a seed of any size."""
    return np.random.SeedSequence([int(seed) & (2 ** 64 - 1), stream]) \
        .generate_state(2, dtype=np.uint32)


def key_of(seed: int, stream: int):
    w = seed_words(seed, stream)
    return jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0),
                                                 int(w[0])), int(w[1]))


def mlp_shapes(d_in: int, hidden: int, d_out: int, n_hidden: int):
    dims = [d_in] + [hidden] * n_hidden + [d_out]
    return [(dims[i], dims[i + 1]) for i in range(len(dims) - 1)]


def _mlp(key, shapes, lead, dtype):
    out = {}
    for i, (a, b) in enumerate(shapes):
        kw, kb = jax.random.split(jax.random.fold_in(key, i))
        out[f"fc{i}"] = {
            "w": (jax.random.normal(kw, lead + (a, b), jnp.float32)
                  / np.sqrt(a)).astype(dtype),
            "b": (0.01 * jax.random.normal(kb, lead + (b,), jnp.float32)
                  ).astype(dtype)}
    return out


def _init(key, *, hidden, layers, head_hidden, head_layers, n_species,
          n_heads, dtype):
    ks = jax.random.split(key, layers + 3)
    shared = {"embed": {"table": (0.02 * jax.random.normal(
        ks[0], (n_species, hidden), jnp.float32)).astype(dtype)}}
    for i in range(layers):
        ke, kh = jax.random.split(ks[1 + i])
        shared[f"layer{i}"] = {
            "phi_e": _mlp(ke, mlp_shapes(2 * hidden + 1, hidden, hidden, 1),
                          (), dtype),
            "phi_h": _mlp(kh, mlp_shapes(2 * hidden, hidden, hidden, 1),
                          (), dtype)}
    lead = (n_heads,)
    heads = {
        "energy": _mlp(ks[-2], mlp_shapes(hidden, head_hidden, 1,
                                          head_layers), lead, dtype),
        "force": _mlp(ks[-1], mlp_shapes(hidden, head_hidden, 3,
                                         head_layers), lead, dtype)}
    return {"shared": shared, "heads": heads}


@functools.lru_cache(maxsize=None)
def _jitted(hidden, layers, head_hidden, head_layers, n_species, n_heads,
            dtype_name):
    return jax.jit(functools.partial(
        _init, hidden=hidden, layers=layers, head_hidden=head_hidden,
        head_layers=head_layers, n_species=n_species, n_heads=n_heads,
        dtype=DTYPES[dtype_name]))


def init_params(cfg: dict, seed: int, device=None):
    """Parameters of configuration ``cfg`` (the config file's dict) for
    ``--seed``, drawn in one jitted call on ``device`` (default device when
    None)."""
    fn = _jitted(cfg["gnn_hidden"], cfg["gnn_layers"], cfg["head_hidden"],
                 cfg["head_layers"], cfg["n_species"], cfg["n_tasks"],
                 cfg["param_dtype"])
    key = key_of(seed, 0)
    if device is not None:
        key = jax.device_put(key, device)
    return fn(key)
