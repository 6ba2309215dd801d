"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, bit-exactness.

Interpret mode is chosen by backend, so on the CPU every kernel runs
interpreted and must match its ref.py oracle exactly (integer kernels)
or to fp tolerance (flash attention).  test_tpu_compile.py compiles the
serving kernels for the TPU.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quant import quantize
from repro.kernels import interpret_mode
from repro.kernels.bts_encode.ops import bts_encode
from repro.kernels.bts_encode.ref import bts_encode_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.int8_matmul.ops import int8_matmul
from repro.kernels.int8_matmul.ref import int8_matmul_ref
from repro.kernels.paged_attention.ops import (
    dense_attention_decode, paged_attention_decode, paged_attention_prefill,
)
from repro.kernels.paged_attention.ref import (
    dense_decode_ref, paged_decode_ref, paged_prefill_ref,
)
from repro.kernels.rglru_scan.ops import rglru_scan
from repro.kernels.rglru_scan.ref import rglru_scan_ref
from repro.kernels.stoch_matmul.ops import stoch_matmul, stoch_matmul_packed
from repro.kernels.stoch_matmul.ref import (
    encode_operands, stoch_matmul_packed_ref, stoch_matmul_ref,
)


def test_interpret_mode_chosen_by_backend():
    assert interpret_mode() is (jax.default_backend() != "tpu")
    assert interpret_mode(True) is True
    assert interpret_mode(False) is False


# ------------------------------------------------------------- stoch_matmul
@pytest.mark.parametrize("m,k,n", [(32, 32, 32), (16, 48, 8), (33, 17, 5), (64, 96, 32)])
def test_stoch_matmul_kernel_bit_exact(rng, m, k, n):
    xq = jnp.asarray(rng.integers(-127, 128, (m, k)), jnp.int8)
    wq = jnp.asarray(rng.integers(-127, 128, (k, n)), jnp.int8)
    xs, sx, ws, sw = encode_operands(xq, wq)
    got = stoch_matmul_packed(xs, sx, ws, sw)
    want = stoch_matmul_packed_ref(xs, sx, ws, sw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("x_gen,w_gen", [("thermometer", "bresenham"), ("lfsr", "bresenham"), ("thermometer", "lfsr")])
def test_stoch_matmul_generators(rng, x_gen, w_gen):
    xq = quantize(jnp.asarray(rng.standard_normal((24, 40)), jnp.float32))
    wq = quantize(jnp.asarray(rng.standard_normal((40, 12)), jnp.float32), axis=0)
    got = stoch_matmul(xq, wq, x_gen=x_gen, w_gen=w_gen)
    want = stoch_matmul_ref(xq, wq, x_gen=x_gen, w_gen=w_gen)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


@pytest.mark.parametrize("bm,bn,bk", [(8, 8, 8), (16, 32, 8), (32, 32, 32)])
def test_stoch_matmul_blocking_invariance(rng, bm, bn, bk):
    """BlockSpec tiling must not change the result."""
    xq = jnp.asarray(rng.integers(-127, 128, (32, 32)), jnp.int8)
    wq = jnp.asarray(rng.integers(-127, 128, (32, 16)), jnp.int8)
    xs, sx, ws, sw = encode_operands(xq, wq)
    want = stoch_matmul_packed_ref(xs, sx, ws, sw)
    got = stoch_matmul_packed(xs, sx, ws, sw, bm=bm, bn=bn, bk=bk)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -------------------------------------------------------------- int8_matmul
@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (64, 256, 32), (100, 70, 9)])
def test_int8_matmul_kernel(rng, m, k, n):
    xq = quantize(jnp.asarray(rng.standard_normal((m, k)), jnp.float32))
    wq = quantize(jnp.asarray(rng.standard_normal((k, n)), jnp.float32), axis=0)
    got = int8_matmul(xq, wq)
    want = int8_matmul_ref(xq, wq)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_int8_matmul_saturating_inputs():
    x = jnp.full((8, 16), 127, jnp.int8)
    from repro.core.quant import QTensor
    xq = QTensor(x, jnp.float32(1.0))
    wq = QTensor(-x.T.reshape(16, 8), jnp.float32(1.0))
    got = int8_matmul(xq, wq)
    want = int8_matmul_ref(xq, wq)  # -127*127*16 accumulations: needs int32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0)


# ---------------------------------------------------------------- bts_encode
@pytest.mark.parametrize("gen", ["thermometer", "bresenham", "lfsr"])
@pytest.mark.parametrize("shape", [(64, 64), (65, 3), (7, 129)])
def test_bts_encode_kernel(rng, gen, shape):
    q = jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
    words, sign = bts_encode(q, generator=gen)
    words_ref, sign_ref = bts_encode_ref(q, generator=gen)
    np.testing.assert_array_equal(np.asarray(words), np.asarray(words_ref))
    np.testing.assert_array_equal(np.asarray(sign), np.asarray(sign_ref))


# --------------------------------------------------------- paged attention
def _paged_setup(rng, b, kvh, g, hd, bs, w, n_blocks):
    q = jnp.asarray(rng.standard_normal((b, kvh * g, hd)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((n_blocks, kvh, bs, hd)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((n_blocks, kvh, bs, hd)), jnp.float32)
    table = jnp.asarray(rng.integers(1, n_blocks, (b, w)), jnp.int32)
    return q, kp, vp, table


def _int8_pool(rng, kp, vp):
    """Random int8 pools with per-KV-head scales, like ``QuantPagedKVCache``."""
    kvh = kp.shape[1]
    kq, vq = (jnp.asarray(rng.integers(-127, 128, x.shape), jnp.int8) for x in (kp, vp))
    ks, vs = (jnp.asarray(rng.uniform(0.005, 0.05, kvh), jnp.float32) for _ in range(2))
    return kq, vq, ks, vs


@pytest.mark.parametrize("g", [1, 2, 4])
@pytest.mark.parametrize("softcap", [0.0, 5.0])
@pytest.mark.parametrize("pool", ["f32", "int8"])
def test_paged_decode_kernel_vs_ref(rng, g, softcap, pool):
    """Streamed decode vs the gathered-view oracle; one batch row per
    kv_len boundary: empty (a slot still prefilling), single token, exact
    block edge, one past it, and the full table extent.  The int8 pool is
    checked against the oracle on the pool dequantized by its per-head
    scales."""
    kvh, hd, bs, w = 2, 16, 4, 3
    kv_len = jnp.asarray([0, 1, bs, bs + 1, w * bs], jnp.int32)
    q, kp, vp, table = _paged_setup(rng, kv_len.shape[0], kvh, g, hd, bs, w, 16)
    ks = vs = None
    if pool == "int8":
        kp, vp, ks, vs = _int8_pool(rng, kp, vp)
    got = paged_attention_decode(q, kp, vp, table, kv_len, ks, vs, softcap=softcap)
    want = paged_decode_ref(q, kp, vp, table, kv_len, softcap=softcap, k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_paged_decode_kernel_ring_layout(rng):
    """Windowed-ring layout: KV written through ``_paged_write_token`` in
    wrapped ring order must read back identically through the streamed
    kernel and the gathered ``_paged_view`` + ``_sdpa`` path."""
    from repro.models.attention import PagedKVCache, _paged_view, _paged_write_token, _sdpa

    b, kvh, g, hd, bs, ring_blocks = 2, 2, 2, 16, 4, 2
    ring = ring_blocks * bs
    cache = PagedKVCache(jnp.zeros((8, kvh, bs, hd)), jnp.zeros((8, kvh, bs, hd)))
    table = jnp.asarray([[1, 2], [5, 3]], jnp.int32)
    # write past the wrap point: positions 0..ring+2 land at slot pos % ring
    for pos in range(ring + 3):
        kn = jnp.asarray(rng.standard_normal((b, kvh, 1, hd)), jnp.float32)
        vn = jnp.asarray(rng.standard_normal((b, kvh, 1, hd)), jnp.float32)
        cache = _paged_write_token(cache, table, jnp.full((b,), pos % ring, jnp.int32), kn, vn)
    kv_len = jnp.full((b,), ring, jnp.int32)  # ring full: every slot valid
    q = jnp.asarray(rng.standard_normal((b, kvh * g, hd)), jnp.float32)
    got = paged_attention_decode(q, cache.k, cache.v, table, kv_len)
    k_log, v_log = _paged_view(cache, table)
    want = _sdpa(q[:, :, None], k_log, v_log, causal=False, window=0, kv_len=kv_len)[:, :, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("g,softcap", [(1, 0.0), (2, 3.0)])
def test_paged_prefill_kernel_vs_ref(rng, g, softcap):
    """Causal suffix prefill: starts at 0, mid-block, and block edges."""
    kvh, hd, bs, w, s = 2, 16, 4, 4, 3
    start = jnp.asarray([0, 2, bs - 1, bs, 2 * bs + 1], jnp.int32)
    q, kp, vp, table = _paged_setup(rng, start.shape[0], kvh, g, hd, bs, w, 24)
    qs = jnp.asarray(rng.standard_normal((start.shape[0], kvh * g, s, hd)), jnp.float32)
    got = paged_attention_prefill(qs, kp, vp, table, start, softcap=softcap)
    want = paged_prefill_ref(qs, kp, vp, table, start, softcap=softcap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_dense_decode_kernel_vs_ref(rng):
    """Length-masked dense decode, incl. a partial trailing key block
    (S not a multiple of bk) and per-slot kv_len boundaries."""
    kvh, g, hd, sk = 2, 2, 16, 11
    kv_len = jnp.asarray([0, 1, 4, 5, 11], jnp.int32)
    b = kv_len.shape[0]
    q = jnp.asarray(rng.standard_normal((b, kvh * g, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, kvh, sk, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, kvh, sk, hd)), jnp.float32)
    got = dense_attention_decode(q, k, v, kv_len, bk=4)
    want = dense_decode_ref(q, k, v, kv_len)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("g", [1, 2, 4])
@pytest.mark.parametrize("kv_len", [[3, 9], [0, 1, 4, 12]], ids=["mid", "edges"])
def test_paged_decode_kernel_bf16(rng, g, kv_len):
    kvh, hd, bs, w = 2, 16, 4, 3
    kv_len = jnp.asarray(kv_len, jnp.int32)
    q, kp, vp, table = _paged_setup(rng, kv_len.shape[0], kvh, g, hd, bs, w, 8)
    q, kp, vp = (x.astype(jnp.bfloat16) for x in (q, kp, vp))
    got = paged_attention_decode(q, kp, vp, table, kv_len)
    assert got.dtype == jnp.bfloat16
    want = paged_decode_ref(q.astype(jnp.float32), kp, vp, table, kv_len)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want), atol=0.05)


# ------------------------------------------------------------ flash attention
@pytest.mark.parametrize("sq,sk,causal,window", [
    (128, 128, True, 0),
    (256, 256, True, 64),
    (130, 130, True, 0),     # padding path
    (64, 64, True, 16),
])
def test_flash_attention_vs_ref(rng, sq, sk, causal, window):
    b, h, d = 2, 2, 32
    q = jnp.asarray(rng.standard_normal((b, h, sq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h, sk, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, sk, d)), jnp.float32)
    got = flash_attention(q, k, v, causal=causal, window=window, bq=64, bk=64)
    want = attention_ref(
        q.reshape(b * h, sq, d), k.reshape(b * h, sk, d), v.reshape(b * h, sk, d),
        scale=d ** -0.5, causal=causal, window=window,
    ).reshape(b, h, sq, d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("hq,hkv,s,window", [
    (8, 2, 128, 0),
    (4, 1, 64, 16),   # window + fold
    (6, 3, 72, 0),    # folded rows (g*s=144) not a block multiple: pad path
])
def test_flash_attention_gqa(rng, hq, hkv, s, window):
    """Hq != Hkv runs group-folded (no repeated K/V): the kernel must
    recover true query positions through the fold period."""
    b, d = 2, 16
    q = jnp.asarray(rng.standard_normal((b, hq, s, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.float32)
    got = flash_attention(q, k, v, causal=True, window=window, bq=64, bk=64)
    kr = jnp.repeat(k, hq // hkv, axis=1).reshape(b * hq, s, d)
    vr = jnp.repeat(v, hq // hkv, axis=1).reshape(b * hq, s, d)
    want = attention_ref(q.reshape(b * hq, s, d), kr, vr, scale=d ** -0.5,
                         causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got).reshape(b * hq, s, d), np.asarray(want), atol=2e-5)


def test_flash_attention_softcap(rng):
    """Logit softcap (tanh(s/c)*c, pre-mask) must match the _sdpa order."""
    from repro.models.attention import _sdpa

    b, h, s, d, cap = 1, 2, 64, 16, 4.0
    q = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    got = flash_attention(q, k, v, causal=True, softcap=cap, bq=64, bk=64)
    want = _sdpa(q, k, v, causal=True, window=0, softcap=cap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_flash_attention_bf16(rng):
    b, h, s, d = 1, 2, 128, 32
    q = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.bfloat16)
    got = flash_attention(q, k, v, causal=True)
    want = attention_ref(
        *(x.astype(jnp.float32).reshape(b * h, s, d) for x in (q, k, v)),
        scale=d ** -0.5, causal=True,
    ).reshape(b, h, s, d)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want), atol=0.05
    )


# ------------------------------------------------------------------- rglru
@pytest.mark.parametrize("b,s,d,chunk", [(2, 64, 16, 16), (3, 100, 8, 32), (1, 16, 4, 64)])
def test_rglru_scan_kernel(rng, b, s, d, chunk):
    a = jnp.asarray(rng.uniform(0.2, 0.999, (b, s, d)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((b, s, d)), jnp.float32)
    got = rglru_scan(a, x, chunk=chunk)
    want = rglru_scan_ref(a, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
