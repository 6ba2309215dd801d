"""Seeded model weights, made on the device in one jitted call.

The tree is the configuration's family's (``tree`` of
``bench/families/<model_type>.py``): the serving engine's parameter
layout and the dtypes it serves in.  The values are a pure function of
the configuration file and the seed, so the reference
(``bench/reference.py``) regenerates the same weights itself instead of
taking anything the program made.  ``normal`` and ``norm`` are the
pieces families build their trees from.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

BF16 = jnp.bfloat16
F32 = jnp.float32


def seed_key(seed: int) -> jax.Array:
    """A PRNG key that keeps every bit of a seed wider than 32 bits."""
    if seed < 0:
        raise ValueError(f"seed={seed} must be >= 0")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, F32) * scale).astype(dtype)


def norm(key, n_layers, d, kind):
    """Norm parameters near identity, randomised so that a reference that
    ignored them would disagree."""
    k1, k2 = jax.random.split(key)
    lead = (n_layers,) if n_layers else ()
    p = {"scale": 1.0 + normal(k1, lead + (d,), 0.1, F32)}
    if kind == "layernorm":
        p["bias"] = normal(k2, lead + (d,), 0.02, F32)
    return p


def make(family, cfg: Dict[str, Any], seed: int, device=None) -> Dict[str, Any]:
    """The configuration's weights for ``seed``, on ``device`` (default:
    the first device), from one jitted call."""
    fn = jax.jit(lambda k: family.tree(cfg, k))
    key = seed_key(seed)
    if device is not None:
        key = jax.device_put(key, device)
    return fn(key)


def shapes(family, cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The tree's shapes and dtypes, with nothing allocated."""
    return jax.eval_shape(lambda k: family.tree(cfg, k), seed_key(0))
