"""Pallas kernel: int8 x int8 -> int32 matmul — ASTRA's expectation fast path.

This is the TPU-native translation of ASTRA's insight (DESIGN.md §2): all
GEMMs — including dynamic-operand attention GEMMs — run in symmetric int8
with wide accumulation and a single output requantization ("one ADC at the
output").  Output-stationary: the int32 accumulator tile lives in VMEM and
is written once after the K loop.

Blocks default to 128x128x128: MXU-aligned (128 systolic dims), int8 tiles
of 16 KiB each and a 64 KiB fp32/int32 accumulator — comfortably in VMEM.
Grid = (M/bm, N/bn, K/bk), K innermost/sequential.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode


def _kernel(x_ref, w_ref, o_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jax.lax.dot_general(
        x_ref[...],
        w_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def int8_matmul_kernel(
    x: jax.Array,  # [M, K] int8
    w: jax.Array,  # [K, N] int8
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    m, k = x.shape
    k2, n = w.shape
    assert k == k2 and m % bm == 0 and n % bn == 0 and k % bk == 0
    return pl.pallas_call(
        _kernel,
        grid=(m // bm, n // bn, k // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret_mode(interpret),
    )(x, w)
