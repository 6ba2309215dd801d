"""Public wrapper with shape padding for the linear-recurrence kernel."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.rglru_scan.kernel import rglru_scan_kernel


@functools.partial(jax.jit, static_argnames=("bb", "chunk", "interpret"))
def rglru_scan(a: jax.Array, b: jax.Array, *, bb: int = 8, chunk: int = 256,
               interpret: Optional[bool] = None):
    bsz, s, d = a.shape
    bb = min(bb, bsz)
    chunk = min(chunk, s)
    pb, ps = (-bsz) % bb, (-s) % chunk
    if pb or ps:
        # pad decays with 1 and inputs with 0: padded steps keep state
        a = jnp.pad(a, ((0, pb), (0, ps), (0, 0)), constant_values=1)
        b = jnp.pad(b, ((0, pb), (0, ps), (0, 0)))
    out = rglru_scan_kernel(a, b, bb=bb, chunk=chunk, interpret=interpret)
    return out[:bsz, :s]
