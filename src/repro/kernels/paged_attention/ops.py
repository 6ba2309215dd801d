"""Public paged-attention wrappers: GQA grouping, normalization, dtypes.

Three entry points over the streaming kernels of ``kernel.py``:

* :func:`paged_attention_decode`  — one token per slot against the pooled
  KV blocks (global causal and windowed-ring layouts: both reduce to
  length masking at decode time);
* :func:`paged_attention_prefill` — packed multi-token suffixes, causal
  against each slot's absolute ``start`` offset, past KV read straight
  from the pool (prefix-cache and chunked-prefill admission);
* :func:`dense_attention_decode`  — the dense per-slot cache layout,
  length-masked instead of full-``max_len``.

Queries arrive in the model's ``[B, H, ...]`` head layout; the wrappers
fold them into per-KV-head groups (no K/V repetition) and normalize the
kernel's un-normalized accumulator by the softmax denominator.  Inputs
are cast to the cache dtype (the engine keeps the two equal — KV dtype
follows model dtype); accumulation is fp32 inside the kernel and the
output is returned in the query dtype.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention.kernel import (
    dense_attention_kernel, paged_attention_kernel, paged_attention_kernel_prefill,
)


def _normalize(o, l):
    return o / jnp.maximum(l, 1e-30)


@functools.partial(jax.jit, static_argnames=("softcap", "interpret"))
def paged_attention_decode(
    q: jax.Array,       # [B, H, hd] one query token per slot
    k_pool: jax.Array,  # [n_blocks, KVH, bs, hd]
    v_pool: jax.Array,  # [n_blocks, KVH, bs, hd]
    table: jax.Array,   # [B, W] int32
    kv_len: jax.Array,  # [B] int32 valid positions per slot (0 -> zeros out)
    k_scale: jax.Array = None,  # [KVH] f32: required for int8 pools
    v_scale: jax.Array = None,  # [KVH] f32
    *,
    softcap: float = 0.0,
    interpret: Optional[bool] = None,
) -> jax.Array:
    b, h, hd = q.shape
    kvh = k_pool.shape[1]
    g = h // kvh
    # int8 pools: queries stay float (the kernel dequantizes K/V per block)
    qd = jnp.float32 if k_pool.dtype == jnp.int8 else k_pool.dtype
    qg = q.astype(qd).reshape(b, kvh, g, hd)
    o, _, l = paged_attention_kernel(
        qg, k_pool, v_pool, jnp.asarray(table, jnp.int32),
        jnp.asarray(kv_len, jnp.int32), k_scale, v_scale,
        scale=hd ** -0.5, softcap=softcap, interpret=interpret,
    )
    return _normalize(o, l).reshape(b, h, hd).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("softcap", "interpret"))
def paged_attention_prefill(
    q: jax.Array,       # [B, H, S, hd] packed suffix queries
    k_pool: jax.Array,  # [n_blocks, KVH, bs, hd] (suffix KV already written)
    v_pool: jax.Array,  # [n_blocks, KVH, bs, hd]
    table: jax.Array,   # [B, W_ctx] int32 (sliced to the context bucket)
    start: jax.Array,   # [B] int32 absolute position of each suffix row 0
    k_scale: jax.Array = None,  # [KVH] f32: required for int8 pools
    v_scale: jax.Array = None,  # [KVH] f32
    *,
    softcap: float = 0.0,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Causal suffix attention with pooled past: query ``(b, i)`` sits at
    absolute position ``start[b] + i`` and sees every earlier pooled
    position (its prefix blocks plus its own freshly-written suffix KV).
    Padded suffix rows compute garbage that callers discard — the same
    contract as the gathered ``_sdpa`` path it replaces."""
    b, h, s, hd = q.shape
    kvh = k_pool.shape[1]
    g = h // kvh
    qd = jnp.float32 if k_pool.dtype == jnp.int8 else k_pool.dtype
    qg = q.astype(qd).reshape(b, kvh, g * s, hd)
    o, _, l = paged_attention_kernel_prefill(
        qg, k_pool, v_pool, jnp.asarray(table, jnp.int32),
        jnp.asarray(start, jnp.int32), k_scale, v_scale,
        scale=hd ** -0.5, q_len=s, softcap=softcap, interpret=interpret,
    )
    return _normalize(o, l).reshape(b, h, s, hd).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("softcap", "bk", "interpret"))
def dense_attention_decode(
    q: jax.Array,       # [B, H, hd]
    k: jax.Array,       # [B, KVH, S, hd] dense slot cache
    v: jax.Array,       # [B, KVH, S, hd]
    kv_len: jax.Array,  # [B] int32
    *,
    softcap: float = 0.0,
    bk: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    b, h, hd = q.shape
    kvh = k.shape[1]
    g = h // kvh
    qg = q.astype(k.dtype).reshape(b, kvh, g, hd)
    o, _, l = dense_attention_kernel(
        qg, k, v, jnp.asarray(kv_len, jnp.int32), scale=hd ** -0.5,
        bk=min(bk, k.shape[2]), softcap=softcap, interpret=interpret,
    )
    return _normalize(o, l).reshape(b, h, hd).astype(q.dtype)
