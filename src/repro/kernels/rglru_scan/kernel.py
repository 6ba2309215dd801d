"""Pallas kernel: chunked linear recurrence  h_t = a_t * h_{t-1} + b_t.

The RG-LRU/SSM workhorse (RecurrentGemma).  Grid = (B/bb, S/chunk) with the
chunk dim sequential; the hidden state is carried across chunks in a
revisited carry output block (portable interpret/TPU pattern).  Inside a
chunk the recurrence runs as a log-depth associative scan over the chunk
axis — VPU-friendly, no per-step scalar loop.

VMEM: two [bb, chunk, D] blocks; with bb=8, chunk=256, D=512 fp32 that is
4 MiB high-water.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode


def _assoc(e1, e2):
    a1, b1 = e1
    a2, b2 = e2
    return a1 * a2, b1 * a2 + b2


def _kernel(a_ref, b_ref, o_ref, h_ref):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[...]  # [bb, chunk, D]
    b = b_ref[...]
    # prefix scan within the chunk: h_t for h_{-1}=0
    aa, bb_ = jax.lax.associative_scan(_assoc, (a, b), axis=1)
    # fold in the carry: h_t = aa_t * h_in + bb_t
    h_in = h_ref[...][:, None, :]  # [bb, 1, D]
    h_all = aa * h_in + bb_
    o_ref[...] = h_all
    h_ref[...] = h_all[:, -1, :]


@functools.partial(jax.jit, static_argnames=("bb", "chunk", "interpret"))
def rglru_scan_kernel(
    a: jax.Array,  # [B, S, D] decay in (0, 1]
    b: jax.Array,  # [B, S, D] driven input
    *,
    bb: int = 8,
    chunk: int = 256,
    interpret: Optional[bool] = None,
):
    bsz, s, d = a.shape
    assert bsz % bb == 0 and s % chunk == 0, (bsz, s, bb, chunk)
    o, _h = pl.pallas_call(
        _kernel,
        grid=(bsz // bb, s // chunk),
        in_specs=[
            pl.BlockSpec((bb, chunk, d), lambda i, c: (i, c, 0)),
            pl.BlockSpec((bb, chunk, d), lambda i, c: (i, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bb, chunk, d), lambda i, c: (i, c, 0)),
            pl.BlockSpec((bb, d), lambda i, c: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s, d), a.dtype),
            jax.ShapeDtypeStruct((bsz, d), a.dtype),
        ],
        interpret=interpret_mode(interpret),
    )(a, b)
    return o
