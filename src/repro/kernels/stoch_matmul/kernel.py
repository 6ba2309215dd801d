"""Pallas kernel: the OSSM array — packed-bitstream stochastic matmul.

Computes out[m, n] = sum_k sign(x[m,k]*w[k,n]) * popcount(X[m,k] & W[k,n])
where X, W are 128-bit stochastic streams packed as 4 uint32 words.  The
AND is the optical AND gate; the popcount + signed add is the balanced
photo-charge accumulator; the k-sum is the analog in-situ accumulation of
one VDPE (pass tiling over K falls out of the bk block size).

TPU mapping: bit ops + popcount run on the VPU over int32 lanes; blocks are
chosen so the [bm, bn, bk] AND-popcount working set fits VMEM
(32x32x32 words x 4 B x 4 words = 2 MiB high-water).  The MXU is NOT used —
this kernel is the *fidelity* path; the deployable fast path is
``kernels/int8_matmul``.  Grid = (M/bm, N/bn, K/bk) with K innermost and
sequential ("arbitrary") for output accumulation.

Layout: streams are pre-transposed so both operands are K-contiguous:
  xs: [M, K, 4] uint32,  sx: [M, K] int8   (activation streams + signs)
  ws: [N, K, 4] uint32,  sw: [N, K] int8   (weight streams, transposed)
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode


def _kernel(xs_ref, sx_ref, ws_ref, sw_ref, o_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    xs = xs_ref[...]  # [bm, bk, 4] uint32
    ws = ws_ref[...]  # [bn, bk, 4] uint32
    # optical AND + photodetector popcount: [bm, bn, bk]
    pc = jnp.sum(
        jax.lax.population_count(xs[:, None, :, :] & ws[None, :, :, :]).astype(jnp.int32),
        axis=-1,
    )
    # balanced-PD sign steering
    s = (sx_ref[...].astype(jnp.int32)[:, None, :] * sw_ref[...].astype(jnp.int32)[None, :, :])
    # analog accumulation over this K tile (one VDPE pass group)
    o_ref[...] += jnp.sum(pc * s, axis=-1)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def stoch_matmul_packed_kernel(
    xs: jax.Array,  # [M, K, 4] uint32
    sx: jax.Array,  # [M, K] int8 in {+1, -1}
    ws: jax.Array,  # [N, K, 4] uint32
    sw: jax.Array,  # [N, K] int8
    *,
    bm: int = 32,
    bn: int = 32,
    bk: int = 32,
    interpret: Optional[bool] = None,
) -> jax.Array:
    m, k, w = xs.shape
    n = ws.shape[0]
    assert w == 4 and ws.shape == (n, k, 4), (xs.shape, ws.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    return pl.pallas_call(
        _kernel,
        grid=(m // bm, n // bn, k // bk),
        in_specs=[
            pl.BlockSpec((bm, bk, 4), lambda i, j, kk: (i, kk, 0)),
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bn, bk, 4), lambda i, j, kk: (j, kk, 0)),
            pl.BlockSpec((bn, bk), lambda i, j, kk: (j, kk)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret_mode(interpret),
    )(xs, sx, ws, sw)
