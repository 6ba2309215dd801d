"""Pallas TPU kernels for ASTRA's compute hot-spots.

Each subpackage ships ``kernel.py`` (pl.pallas_call + BlockSpec),
``ops.py`` (jitted public wrapper) and ``ref.py`` (pure-jnp oracle):

* ``stoch_matmul``   — the OSSM array: packed-bitstream AND+popcount matmul
* ``bts_encode``     — B-to-S converter bank (int8 -> packed 128-bit streams)
* ``int8_matmul``    — ASTRA expectation fast path (MXU int8, output-stationary)
* ``flash_attention``— streaming-softmax attention (causal + sliding window)
* ``rglru_scan``     — chunked linear recurrence for RG-LRU/SSM blocks
* ``paged_attention``— gather-free serve-engine decode/suffix-prefill over
  the paged KV pool (block tables as scalar-prefetch operands)

Kernels target TPU (VMEM BlockSpecs, 128-aligned tiles).  Interpret mode
is chosen by backend (:func:`interpret_mode`): compiled on TPU,
interpreted on CPU, where the tests validate each kernel against its
``ref.py``.
"""
from __future__ import annotations

from typing import Optional

import jax


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """Whether a ``pallas_call`` runs interpreted, chosen by backend.

    ``None`` (every wrapper's default) compiles on TPU and interprets
    anywhere else; there is no fallback on TPU — a kernel that does not
    compile fails the run.  An explicit bool is honoured as given.
    """
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
