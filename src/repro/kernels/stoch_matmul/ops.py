"""Jitted public wrappers around the stochastic-matmul Pallas kernel."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.quant import QTensor, STREAM_LEN
from repro.kernels.stoch_matmul.kernel import stoch_matmul_packed_kernel
from repro.kernels.stoch_matmul.ref import encode_operands


def _pad(a: jax.Array, mult: int, axis: int) -> jax.Array:
    pad = (-a.shape[axis]) % mult
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


def stoch_matmul_packed(xs, sx, ws, sw, *, bm=32, bn=32, bk=32, interpret=None):
    """Packed-stream matmul with automatic block padding."""
    m, k = sx.shape
    n = sw.shape[0]
    xs, sx = _pad(_pad(xs, bm, 0), bk, 1), _pad(_pad(sx, bm, 0), bk, 1)
    ws, sw = _pad(_pad(ws, bn, 0), bk, 1), _pad(_pad(sw, bn, 0), bk, 1)
    # padded signs are 0 -> padded lanes contribute nothing
    out = stoch_matmul_packed_kernel(xs, sx, ws, sw, bm=bm, bn=bn, bk=bk, interpret=interpret)
    return out[:m, :n]


@functools.partial(jax.jit, static_argnames=("x_gen", "w_gen", "interpret"))
def stoch_matmul(
    xq: QTensor,
    wq: QTensor,
    x_gen: str = "thermometer",
    w_gen: str = "bresenham",
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Quantized [M,K] @ [K,N] through the OSSM-array kernel, dequantized."""
    xs, sx, ws, sw = encode_operands(xq.q, wq.q, x_gen, w_gen)
    acc = stoch_matmul_packed(xs, sx, ws, sw, interpret=interpret)
    return acc.astype(jnp.float32) * STREAM_LEN * xq.scale * wq.scale
