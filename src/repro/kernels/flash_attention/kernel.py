"""Pallas kernel: streaming-softmax (flash) attention, causal + sliding window.

Serves the prefill path of every attention arch (global and local blocks
share this kernel — ``window=0`` means unbounded causal context).  GQA is
handled by the wrapper (queries grouped per KV head): the ``G`` query
heads sharing a KV head are stacked over the query axis and ``q_len``
tells the kernel the fold period, so each K/V tile is read once per
*group* rather than once per query head.  Logit softcap (``tanh(s/c)*c``,
pre-mask) matches the ``_sdpa`` ordering.

Blocking: grid = (BH, Sq/bq, Sk/bk) with the K dim innermost & sequential.
Online softmax state (running max m, denominator l) and the un-normalized
accumulator are carried across K steps in *output* blocks that are
revisited (portable across interpret mode and TPU; avoids TPU-only scratch
shapes).  The wrapper normalizes and strips the side outputs.

VMEM: bq x d + bk x d tiles + bq x bk score block; 128x128 fp32 blocks +
d<=256 keep the working set ~0.5 MiB.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, *, scale, causal, window,
            bq, bk, q_len, softcap):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref[...], NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0]  # [bq, d]
    k = k_ref[0]  # [bk, d]
    v = v_ref[0]  # [bk, d]
    s = jax.lax.dot_general(
        q, k, dimension_numbers=(((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [bq, bk]
    if softcap > 0:
        s = jnp.tanh(s / softcap) * softcap

    iq = pl.program_id(1)
    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (q.shape[0], k.shape[0]), 0)
    if q_len:
        # GQA fold: query rows are G head groups stacked over q_len real
        # positions — row r of the folded axis sits at position r % q_len
        q_pos = q_pos % q_len
    k_pos = kk * bk + jax.lax.broadcasted_iota(jnp.int32, (q.shape[0], k.shape[0]), 1)
    mask = jnp.ones_like(s, dtype=jnp.bool_)
    if causal:
        mask &= q_pos >= k_pos
    if window > 0:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[0]  # [bq, 1]
    l_prev = l_ref[0]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # guard fully-masked rows (no valid keys yet)
    m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    p = jnp.exp(s - m_safe)
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, jnp.exp(m_prev - m_safe))
    l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    o_new = alpha * o_ref[0] + jax.lax.dot_general(
        p.astype(v.dtype), v, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[0] = m_new
    l_ref[0] = l_new
    o_ref[0] = o_new


@functools.partial(jax.jit, static_argnames=("scale", "causal", "window", "bq",
                                             "bk", "q_len", "softcap", "interpret"))
def flash_attention_kernel(
    q: jax.Array,  # [BH, Sq, D]
    k: jax.Array,  # [BH, Sk, D]
    v: jax.Array,  # [BH, Sk, D]
    *,
    scale: float,
    causal: bool = True,
    window: int = 0,
    bq: int = 128,
    bk: int = 128,
    q_len: int = 0,  # GQA fold period: row r is query position r % q_len (0 = identity)
    softcap: float = 0.0,
    interpret: Optional[bool] = None,
):
    bh, sq, d = q.shape
    sk = k.shape[1]
    assert sq % bq == 0 and sk % bk == 0, (sq, sk, bq, bk)
    kern = functools.partial(_kernel, scale=scale, causal=causal, window=window,
                             bq=bq, bk=bk, q_len=q_len, softcap=softcap)
    o, m, l = pl.pallas_call(
        kern,
        grid=(bh, sq // bq, sk // bk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
            jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(q, k, v)
    return o / jnp.maximum(l, 1e-30), m, l
