"""Weights of a benchmark configuration, made on the device from ``--seed``.

One jitted call of the configuration reference's ``init`` draws every leaf
in the layout the system under test takes. The reference draws the same
leaves from the same seed with the same call, so it never takes weights the
program has touched.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import harness

DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def seed_words(seed: int, stream: int) -> np.ndarray:
    """Two uint32 words for stream ``stream`` of a seed of any size."""
    return np.random.SeedSequence([int(seed) & (2 ** 64 - 1), stream]) \
        .generate_state(2, dtype=np.uint32)


def key_of(seed: int, stream: int):
    w = seed_words(seed, stream)
    return jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0),
                                                 int(w[0])), int(w[1]))


@functools.lru_cache(maxsize=None)
def _jitted(ref, cfg_key: str):
    return jax.jit(functools.partial(ref.init, json.loads(cfg_key)))


def init_params(cfg: dict, seed: int, device=None):
    """Parameters of configuration ``cfg`` (the config file's dict) for
    ``--seed``, drawn in one jitted call on ``device`` (default device when
    None)."""
    fn = _jitted(harness.reference(cfg), json.dumps(cfg, sort_keys=True))
    key = key_of(seed, 0)
    if device is not None:
        key = jax.device_put(key, device)
    return fn(key)
