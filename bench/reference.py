"""The plain reference: a configuration's forward pass in float32, in
straightforward ``jax.numpy``, with no kernel, cache, paging or batching
of the program's.  It imports nothing of the program: its weights come
from ``bench.weights`` (the same seeded values the engine serves), and
its equations from the configuration's family
(``bench/families/<model_type>.py``), whose ``forward`` may use the
shared pieces here:

* ``attention``: global causal GQA, rotary on the first
  ``partial_rotary_factor`` of each head as interleaved pairs;
* ``norm``: LayerNorm or RMSNorm in float32;
* ``mm``: a weight GEMM, in float32 or the control's.

Every matmul runs at ``highest`` precision.  ``control`` names a
control: every weight GEMM (projections, FFN, LM head) with weights
scaled per output column and activations per row into ``"int8"``
(symmetric, round to nearest) or ``"fp8"`` (float8 e4m3), products
accumulated in float32: the steps below the bf16 the configuration
states.
"""
from __future__ import annotations

import functools
import json
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


FP8_MAX = 448.0  # largest finite float8_e4m3fn
INT8_MAX = 127.0
CONTROLS = ("int8", "fp8")


def _q(x, axis, control):
    """``x`` scaled along ``axis`` into the control's type and rounded:
    the rounded values in float32 (exact) and the scales."""
    top = FP8_MAX if control == "fp8" else INT8_MAX
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30) / top
    if control == "fp8":
        return (x / s).astype(jnp.float8_e4m3fn).astype(F32), s
    if control == "int8":
        return jnp.clip(jnp.round(x / s), -top, top), s
    raise ValueError(f"unknown control {control!r}; one of {CONTROLS}")


def mm(x, w, control):
    """x [..., d_in] @ w [d_in, d_out] in float32, or the control's."""
    w = w.astype(F32)
    if control is None:
        return x @ w
    xq, xs = _q(x, -1, control)
    wq, ws = _q(w, 0, control)
    return (xq @ wq) * xs * ws


def norm(p, x, kind, eps):
    if kind == "rmsnorm":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    else:
        mu = jnp.mean(x, -1, keepdims=True)
        y = (x - mu) * jax.lax.rsqrt(jnp.mean((x - mu) ** 2, -1, keepdims=True) + eps)
    y = y * p["scale"].astype(F32)
    return y + p["bias"].astype(F32) if "bias" in p else y


def rope(x, pct, theta):
    """x [B, S, H, hd]; interleaved pairs over the first ``pct`` of hd."""
    hd = x.shape[-1]
    rot = int(hd * pct) // 2 * 2
    freqs = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=F32) / rot))
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * freqs  # [S, rot/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., 0:rot:2], x[..., 1:rot:2]
    y = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1).reshape(*x.shape[:-1], rot)
    return jnp.concatenate([y, x[..., rot:]], -1)


def attention(cfg, p, h, control):
    """Global causal GQA over ``h [B, S, D]`` with the projections ``p``
    (``wq``, ``wk``, ``wv``, ``wo``)."""
    b, s, _ = h.shape
    nh, kvh, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = mm(h, p["wq"]["w"], control).reshape(b, s, nh, hd)
    k = mm(h, p["wk"]["w"], control).reshape(b, s, kvh, hd)
    v = mm(h, p["wv"]["w"], control).reshape(b, s, kvh, hd)
    q = rope(q, cfg["partial_rotary_factor"], cfg["rope_theta"])
    k = rope(k, cfg["partial_rotary_factor"], cfg["rope_theta"])
    q = q.reshape(b, s, kvh, nh // kvh, hd)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k) * hd ** -0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    o = jnp.einsum("bkgqs,bskd->bqkgd", jax.nn.softmax(scores, -1), v)
    return mm(o.reshape(b, s, nh * hd), p["wo"]["w"], control)


@functools.partial(jax.jit, static_argnames=("forward", "cfg_json", "control"))
def _forward(w, tokens, positions, forward, cfg_json, control):
    with jax.default_matmul_precision("highest"):
        return forward(json.loads(cfg_json), w, tokens, positions, control)


def logits(family, cfg: Dict, w, tokens: np.ndarray, positions: np.ndarray,
           control: Optional[str] = None,
           columns: Optional[Dict[str, jax.Array]] = None) -> jax.Array:
    """Float32 logits ``[B, G, V]`` at ``positions [B, G]`` of the causal
    forward over ``tokens [B, S]`` by the family's equations; with
    ``control``, that control's.  ``columns``, where given, receives the
    family's named per-position columns ``[B, G]`` beside the logits."""
    out, cols = _forward(w, jnp.asarray(tokens, jnp.int32), jnp.asarray(positions, jnp.int32),
                         forward=family.forward, cfg_json=json.dumps(cfg, sort_keys=True),
                         control=control)
    if columns is not None:
        columns.update(cols)
    return out
