"""Public wrapper for the B-to-S encoder kernel (pads to block multiples)."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.bts_encode.kernel import bts_encode_kernel


@functools.partial(jax.jit, static_argnames=("generator", "br", "bc", "interpret"))
def bts_encode(q: jax.Array, generator: str = "bresenham", br: int = 64, bc: int = 64,
               interpret: Optional[bool] = None):
    r, c = q.shape
    br, bc = min(br, r), min(bc, c)
    pr, pc = (-r) % br, (-c) % bc
    if pr or pc:
        q = jnp.pad(q, ((0, pr), (0, pc)))
    words, sign = bts_encode_kernel(q, generator=generator, br=br, bc=bc, interpret=interpret)
    return words[:r, :c], sign[:r, :c]
