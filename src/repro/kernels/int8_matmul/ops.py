"""Jitted public wrapper: quantized matmul with output dequantization."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.quant import QTensor
from repro.kernels.int8_matmul.kernel import int8_matmul_kernel


def _pad(a, mult, axis):
    pad = (-a.shape[axis]) % mult
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def int8_matmul(xq: QTensor, wq: QTensor, *, bm=128, bn=128, bk=128, interpret=None) -> jax.Array:
    m, k = xq.q.shape
    n = wq.q.shape[1]
    x = _pad(_pad(xq.q, bm, 0), bk, 1)
    w = _pad(_pad(wq.q, bk, 0), bn, 1)
    acc = int8_matmul_kernel(x, w, bm=bm, bn=bn, bk=bk, interpret=interpret)[:m, :n]
    return acc.astype(jnp.float32) * xq.scale * wq.scale
