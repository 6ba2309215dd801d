"""Public flash-attention wrapper: GQA folding, padding, head layout."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_kernel


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "bq", "bk", "interpret"))
def flash_attention(
    q: jax.Array,  # [B, Hq, Sq, D]
    k: jax.Array,  # [B, Hkv, Sk, D]
    v: jax.Array,  # [B, Hkv, Sk, D]
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    bq: int = 128,
    bk: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    assert hq % hkv == 0
    scale = d ** -0.5
    # GQA: fold the G query heads sharing each KV head over the query axis
    # (rows [g*Sq + i] of pair (b, kvh)) — K/V are never repeated; the
    # kernel recovers true positions via the q_len fold period
    g = hq // hkv
    qf = q.reshape(b * hkv, g * sq, d)
    kf = k.reshape(b * hkv, sk, d)
    vf = v.reshape(b * hkv, sk, d)
    # pad sequence dims to block multiples; padded keys are masked by causal
    # + explicit key-validity (padded queries discarded on slice-out)
    pq, pk = (-g * sq) % bq, (-sk) % bk
    if pq:
        qf = jnp.pad(qf, ((0, 0), (0, pq), (0, 0)))
    if pk:
        kf = jnp.pad(kf, ((0, 0), (0, pk), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pk), (0, 0)))
    eff_window = window
    if not causal and pk:
        # non-causal path must not attend to padded keys; emulate with a
        # window covering exactly the valid span (encoder use is full-span)
        raise NotImplementedError("non-causal padding unsupported; pad inputs to block size")
    o, _, _ = flash_attention_kernel(
        qf, kf, vf, scale=scale, causal=causal, window=eff_window, bq=bq, bk=bk,
        q_len=sq, softcap=softcap, interpret=interpret,
    )
    return o[:, : g * sq].reshape(b, hkv, g, sq, d).reshape(b, hq, sq, d).astype(q.dtype)
