"""Pallas kernel: the B-to-S converter bank (binary -> stochastic streams).

Converts int8 sign-magnitude operands into packed 128-bit streams
(4 uint32 words) + sign lanes — the electronic front-end of every VDPE
(paper Fig. 3: "B-to-S circuits and serializers").  Pure VPU integer work;
each grid cell encodes a [rows, cols] tile into [rows, cols, 4] words.

Generators match ``core.bitstream``: thermometer (unary counter), bresenham
(clock-division with the round-to-nearest phase preset), and lfsr (the
7-bit maximal-LFSR comparator — realized as a constant visit-order table the
compiler folds into the kernel).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.bitstream import LFSR_ORDER
from repro.kernels import interpret_mode

STREAM_LEN = 128
N_WORDS = 4


def _encode_words(mag: jax.Array, order: jax.Array, generator: str) -> jax.Array:
    """mag [r, c] int32, order [128] visit table -> packed [r, c, 4] uint32."""
    r, c = mag.shape
    i = jax.lax.broadcasted_iota(jnp.int32, (r, c, N_WORDS, 32), 2) * 32 + jax.lax.broadcasted_iota(
        jnp.int32, (r, c, N_WORDS, 32), 3
    )
    m = mag[:, :, None, None]
    if generator == "thermometer":
        bits = (i < m).astype(jnp.uint32)
    elif generator == "bresenham":
        off = STREAM_LEN // 2
        bits = (((i + 1) * m + off) // STREAM_LEN - (i * m + off) // STREAM_LEN).astype(jnp.uint32)
    elif generator == "lfsr":
        bits = (order[i] < m).astype(jnp.uint32)
    else:
        raise ValueError(generator)
    shifts = jax.lax.broadcasted_iota(jnp.uint32, (r, c, N_WORDS, 32), 3)
    return jnp.sum(bits << shifts, axis=-1, dtype=jnp.uint32)


def _kernel(q_ref, order_ref, words_ref, sign_ref, *, generator):
    q = q_ref[...].astype(jnp.int32)
    words_ref[...] = _encode_words(jnp.abs(q), order_ref[...], generator)
    sign_ref[...] = jnp.where(q < 0, -1, 1).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("generator", "br", "bc", "interpret"))
def bts_encode_kernel(q: jax.Array, *, generator="bresenham", br=64, bc=64, interpret=None):
    r, c = q.shape
    assert r % br == 0 and c % bc == 0
    kern = functools.partial(_kernel, generator=generator)
    # LFSR visit table rides along as a tiny replicated input (Pallas
    # kernels cannot capture constant arrays)
    order = jnp.asarray(LFSR_ORDER, jnp.int32)
    return pl.pallas_call(
        kern,
        grid=(r // br, c // bc),
        in_specs=[
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
            pl.BlockSpec((STREAM_LEN,), lambda i, j: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((br, bc, N_WORDS), lambda i, j: (i, j, 0)),
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, c, N_WORDS), jnp.uint32),
            jax.ShapeDtypeStruct((r, c), jnp.int8),
        ],
        interpret=interpret_mode(interpret),
    )(q, order)
