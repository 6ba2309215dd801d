"""Seeded model weights, made on the device in one jitted call.

The tree uses the serving engine's parameter layout (stacked layers under
``units/slot0``) and the dtypes it serves in: GEMM, expert and embedding
weights in bfloat16, norms and the MoE router in float32.  The values are
a pure function of the configuration file and the seed, so the reference
(``bench/reference.py``) regenerates the same weights itself instead of
taking anything the program made.
"""
from __future__ import annotations

import math
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


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, F32) * scale).astype(dtype)


def _norm(key, n_layers, d, kind):
    """Norm parameters near identity, randomised so that a reference that
    ignored them would disagree."""
    k1, k2 = jax.random.split(key)
    lead = (n_layers,) if n_layers else ()
    p = {"scale": 1.0 + _normal(k1, lead + (d,), 0.1, F32)}
    if kind == "layernorm":
        p["bias"] = _normal(k2, lead + (d,), 0.02, F32)
    return p


def _tree(cfg: Dict[str, Any], key) -> Dict[str, Any]:
    L, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    h, kvh, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    v, norm = cfg["vocab_size"], cfg["norm"]
    q_dim, kv_dim = h * hd, kvh * hd
    ks = iter(jax.random.split(key, 16))
    dense = lambda din, dout: {"w": _normal(next(ks), (L, din, dout), din ** -0.5, BF16)}
    layer = {
        "pre_norm": _norm(next(ks), L, d, norm),
        "post_norm": _norm(next(ks), L, d, norm),
        "core": {"wq": dense(d, q_dim), "wk": dense(d, kv_dim),
                 "wv": dense(d, kv_dim), "wo": dense(q_dim, d)},
    }
    f = cfg["intermediate_size"]
    layer["mlp"] = {"up": dense(d, f), "gate": dense(d, f), "down": dense(f, d)}
    tree = {
        "embedding": {"table": _normal(next(ks), (v, d), 0.02, BF16)},
        "head": ({} if cfg["tie_word_embeddings"]
                 else {"w": _normal(next(ks), (d, v), 1 / math.sqrt(d), BF16)}),
        "final_norm": _norm(next(ks), 0, d, norm),
        "units": {"slot0": layer},
    }
    return tree


def make(cfg: Dict[str, Any], seed: int, device=None) -> Dict[str, Any]:
    """The configuration's weights for ``seed``, on ``device`` (default:
    the first device), from one jitted call."""
    fn = jax.jit(lambda k: _tree(cfg, k))
    key = seed_key(seed)
    if device is not None:
        key = jax.device_put(key, device)
    return fn(key)


def shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The tree's shapes and dtypes, with nothing allocated."""
    return jax.eval_shape(lambda k: _tree(cfg, k), seed_key(0))
