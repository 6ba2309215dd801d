"""Pallas kernel: gather-free paged attention over the serve engine's KV pool.

The serve engine's decode hot path used to *materialize* each slot's
logical cache every step — ``pool[table]`` gathers ``[B, W, n_kv, bs, hd]``
into a dense copy, then ``_sdpa`` runs over the whole ``max_len`` extent.
Per-token HBM traffic: read pool + write copy + read copy = 3x the cache
bytes, independent of how much of the table is actually filled.

This kernel streams K/V blocks *directly from the pool* through the block
table instead: the table (and each slot's fill) ride in as scalar-prefetch
operands, so the BlockSpec index map resolves ``table[b, j]`` to a physical
pool block per grid step — no gathered logical view exists anywhere.
Online-softmax state (running max m, denominator l, un-normalized
accumulator) is carried across the block grid in revisited output blocks,
exactly like the flash kernel (portable across interpret mode and TPU).

Two fill-awareness mechanisms compose:

* the index map **clamps** ``j`` to the last live block, so grid steps
  beyond the fill re-request the same block index — Pallas elides the
  copy when consecutive steps map to the same block, so dead table extent
  costs no HBM traffic;
* ``pl.when`` skips the compute for those steps entirely.

Masking modes (one kernel body serves both):

* ``causal=False`` — single-query decode: key position ``< lens[b]``
  (``lens`` = per-slot ``kv_len``).  Global caches pass ``pos+1``; the
  windowed ring passes ``min(pos+1, ring_len)`` — every resident ring
  slot is inside the window and softmax is order-invariant, so length
  masking is exact for both layouts.
* ``causal=True`` — multi-query suffix prefill: query rows are ``G`` head
  groups folded over ``q_len`` suffix positions (row ``r`` is suffix
  position ``r % q_len``), living at absolute position
  ``lens[b] + r % q_len`` (``lens`` = per-slot suffix start); keys are
  masked causally against that absolute position.

GQA is native: the grid runs over KV heads and each step computes all
``G = Hq/Hkv`` query rows against one K/V block — no repeated K/V.
Logit softcap (``tanh(s/c)*c``) is applied pre-mask, matching ``_sdpa``.

Kernels target TPU (VMEM blocks; pick ``bs``/``hd`` 128-aligned for MXU
shapes): compiled there, interpreted elsewhere (``interpret_mode``), and
validated on CPU against ``ref.py``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

NEG_INF = -1e30


def _kernel(lens_ref, ks_ref, vs_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, *,
            scale, causal, q_len, bs, sk, softcap):
    """One (slot, kv-head, block) grid step of streaming-softmax attention.

    q_ref block ``[1, 1, R, hd]`` (R = G query rows, or G*q_len folded for
    prefill); k/v blocks ``[1, 1, bs, hd]``; o/m/l are revisited carry
    blocks.  ``sk`` is the static key extent — positions past it (a
    partial trailing block padded by Pallas) are masked *and* their V rows
    zeroed, because out-of-range block padding is undefined (NaN in
    interpret mode) and ``0 * NaN`` would poison the accumulator.

    ``ks_ref``/``vs_ref`` (static None when the pool is float) are
    per-KV-head scale vectors for int8 pools: each streamed block is
    dequantized *here*, fused into the grid step — no dense dequantized
    view of the cache ever exists.
    """
    b = pl.program_id(0)
    j = pl.program_id(2)
    # scale lookup stays OUTSIDE pl.when: program_id has no lowering rule
    # inside the nested cond jaxpr under interpret mode
    if ks_ref is not None:
        h = pl.program_id(1)
        ks, vs = ks_ref[h], vs_ref[h]

    @pl.when(j == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref[...], NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    n = lens_ref[b]
    live = (j * bs <= n + q_len - 1) if causal else (j * bs < n)

    @pl.when(live)
    def _():
        q = q_ref[0, 0]  # [R, hd]
        k = k_ref[0, 0]  # [bs, hd]
        v = v_ref[0, 0]
        if ks_ref is not None:  # int8 pool: per-block fused dequantize
            k = k.astype(jnp.float32) * ks
            v = v.astype(jnp.float32) * vs
        if sk % bs:  # ragged trailing block possible (dense variant only)
            in_bounds = (j * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, 1), 0)) < sk
            v = jnp.where(in_bounds, v, 0.0)
        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [R, bs]
        if softcap > 0:
            s = jnp.tanh(s / softcap) * softcap
        r = q.shape[0]
        k_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (r, bs), 1)
        if causal:
            q_pos = n + jax.lax.broadcasted_iota(jnp.int32, (r, bs), 0) % q_len
            mask = q_pos >= k_pos
        else:
            mask = k_pos < n
        if sk % bs:
            mask &= k_pos < sk
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[0, 0]  # [R, 1]
        l_prev = l_ref[0, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # guard fully-masked rows (no valid keys yet)
        m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        p = jnp.where(mask, jnp.exp(s - m_safe), 0.0)
        alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, jnp.exp(m_prev - m_safe))
        l_ref[0, 0] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        o_ref[0, 0] = alpha * o_ref[0, 0] + jax.lax.dot_general(
            p.astype(v.dtype), v, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[0, 0] = m_new


def _carry_specs(b, kvh, r, hd, index):
    return (
        [pl.BlockSpec((1, 1, r, hd), index),
         pl.BlockSpec((1, 1, r, 1), index),
         pl.BlockSpec((1, 1, r, 1), index)],
        [jax.ShapeDtypeStruct((b, kvh, r, hd), jnp.float32),
         jax.ShapeDtypeStruct((b, kvh, r, 1), jnp.float32),
         jax.ShapeDtypeStruct((b, kvh, r, 1), jnp.float32)],
    )


@functools.partial(jax.jit, static_argnames=("scale", "causal", "q_len",
                                             "softcap", "interpret"))
def paged_attention_kernel(
    q: jax.Array,       # [B, KVH, R, hd] grouped queries (R = G or G*q_len)
    k_pool: jax.Array,  # [n_blocks, KVH, bs, hd]
    v_pool: jax.Array,  # [n_blocks, KVH, bs, hd]
    table: jax.Array,   # [B, W] int32 logical->physical block ids
    lens: jax.Array,    # [B] int32: kv_len (decode) or suffix start (causal)
    k_scale: jax.Array = None,  # [KVH] f32 per-head scales (int8 pools)
    v_scale: jax.Array = None,  # [KVH] f32
    *,
    scale: float,
    causal: bool = False,
    q_len: int = 1,
    softcap: float = 0.0,
    interpret: Optional[bool] = None,
):
    """Streamed paged attention.  Returns un-normalized (o, m, l).

    Int8 pools (``k_pool.dtype == int8``) require calibrated per-KV-head
    ``k_scale``/``v_scale`` vectors, ridden in as scalar-prefetch operands
    and applied per streamed block inside the kernel body.
    """
    b, kvh, r, hd = q.shape
    bs = k_pool.shape[2]
    w = table.shape[1]
    quantized = k_pool.dtype == jnp.int8
    if quantized and (k_scale is None or v_scale is None):
        raise ValueError("int8 KV pool needs calibrated k_scale/v_scale")
    kern = functools.partial(_kernel, scale=scale, causal=causal, q_len=q_len,
                             bs=bs, sk=w * bs, softcap=softcap)

    if quantized:
        def body(tbl_ref, lens_ref, ks_ref, vs_ref, *refs):
            return kern(lens_ref, ks_ref, vs_ref, *refs)
    else:
        def body(tbl_ref, lens_ref, *refs):
            return kern(lens_ref, None, None, *refs)

    def kv_index(bi, h, j, tbl, ln, *rest):
        # clamp to the last live block: dead extent re-requests the same
        # physical block, which Pallas does not re-copy (no HBM traffic),
        # and pl.when skips its compute
        last = ((ln[bi] + q_len - 1) if causal
                else jnp.maximum(ln[bi] - 1, 0)) // bs
        return (tbl[bi, jnp.minimum(j, last)], h, 0, 0)

    out_index = lambda bi, h, j, *rest: (bi, h, 0, 0)
    out_specs, out_shape = _carry_specs(b, kvh, r, hd, out_index)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4 if quantized else 2,
        grid=(b, kvh, w),
        in_specs=[
            pl.BlockSpec((1, 1, r, hd), lambda bi, h, j, *rest: (bi, h, 0, 0)),
            pl.BlockSpec((1, 1, bs, hd), kv_index),
            pl.BlockSpec((1, 1, bs, hd), kv_index),
        ],
        out_specs=out_specs,
    )
    operands = (table, lens) + (
        (jnp.asarray(k_scale, jnp.float32), jnp.asarray(v_scale, jnp.float32))
        if quantized else ())
    return pl.pallas_call(
        body, grid_spec=grid_spec, out_shape=out_shape, interpret=interpret_mode(interpret),
    )(*operands, q, k_pool, v_pool)


@functools.partial(jax.jit, static_argnames=("scale", "bk", "softcap", "interpret"))
def dense_attention_kernel(
    q: jax.Array,      # [B, KVH, G, hd] grouped single-token queries
    k: jax.Array,      # [B, KVH, S, hd] dense per-slot cache
    v: jax.Array,      # [B, KVH, S, hd]
    kv_len: jax.Array,  # [B] int32 valid key count per slot
    *,
    scale: float,
    bk: int = 128,
    softcap: float = 0.0,
    interpret: Optional[bool] = None,
):
    """Length-masked single-query decode over dense slot caches — the same
    streaming body, indexed contiguously (no table).  Returns (o, m, l).
    Beats full-extent ``_sdpa`` the same way the paged variant does: key
    blocks past ``kv_len`` are neither copied nor computed.
    """
    b, kvh, g, hd = q.shape
    sk = k.shape[2]
    w = -(-sk // bk)
    kern = functools.partial(_kernel, scale=scale, causal=False, q_len=1,
                             bs=bk, sk=sk, softcap=softcap)

    def body(lens_ref, *refs):
        return kern(lens_ref, None, None, *refs)

    def kv_index(bi, h, j, ln):
        return (bi, h, jnp.minimum(j, jnp.maximum(ln[bi] - 1, 0) // bk), 0)

    out_index = lambda bi, h, j, ln: (bi, h, 0, 0)
    out_specs, out_shape = _carry_specs(b, kvh, g, hd, out_index)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, kvh, w),
        in_specs=[
            pl.BlockSpec((1, 1, g, hd), lambda bi, h, j, ln: (bi, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, hd), kv_index),
            pl.BlockSpec((1, 1, bk, hd), kv_index),
        ],
        out_specs=out_specs,
    )
    return pl.pallas_call(
        body, grid_spec=grid_spec, out_shape=out_shape, interpret=interpret_mode(interpret),
    )(kv_len, q, k, v)
