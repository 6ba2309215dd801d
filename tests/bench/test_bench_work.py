"""Operation and byte counts at stablelm-1.6b's published shapes,
checked against hand-computed values; padding earns no work."""
import json
import os
import sys
import types

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
sys.path.insert(0, ROOT)

from bench import spec, work  # noqa: E402
from bench.probe import DecodeCall, PrefillCall  # noqa: E402


def cfg(name):
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        return json.load(f)


STABLELM = cfg("stablelm-1.6b")
DENSE = spec.load_family(spec.ROOT, STABLELM)
V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_decode_attention_counts():
    # 24 layers x 4 x q_dim 2048 x 100 keys; K,V of 100 tokens x kv_dim
    # 2048 plus q and out, 2 bytes each
    assert work.decode_attn(STABLELM, [100]) == (19_660_800, 19_857_408)
    # two tokens decoded: the sum of each
    two = work.decode_attn(STABLELM, [60, 40])
    assert two == tuple(a + b for a, b in zip(work.decode_attn(STABLELM, [60]),
                                              work.decode_attn(STABLELM, [40])))


def test_prefill_attention_counts():
    # 512 tokens at positions 1024..1535: keys = 512*1024 + 512*513/2
    flops, nbytes = work.prefill_attn(STABLELM, [(1024, 512)])
    assert flops == 128_899_350_528
    assert nbytes == 24 * (2 * 1536 * 2048 + 2 * 512 * 2048) * 2 == 402_653_184
    # the same tokens split into two chunks cost the same operations
    split = work.prefill_attn(STABLELM, [(1024, 200), (1224, 312)])
    assert split[0] == pytest.approx(flops)


def test_model_flops_per_token():
    # stablelm: 24 x (4 x 2048^2 + 3 x 2048 x 5632) + 2048 x 100352
    assert work.params_per_token(DENSE, STABLELM) == 1_438_646_272
    # plus causal attention: 4 x 24 layers x 10 keys x q_dim 2048
    assert work.model_flops(DENSE, STABLELM, [10]) == 2 * 1_438_646_272 + 4 * 24 * 10 * 2048


def test_roofline_bound():
    t, bound = work.least_seconds(*work.decode_attn(STABLELM, [1000]), V5E)
    assert bound == "memory"
    assert t == pytest.approx(work.decode_attn(STABLELM, [1000])[1] / 819e9)


def _readings(cfg_, calls_prefill, calls_decode, max_slots):
    trace = types.SimpleNamespace(op_seconds=lambda *a: 0.01, program_seconds=lambda *a: 0.1,
                                  window_s=1.0)
    return types.SimpleNamespace(cfg=cfg_, cell=types.SimpleNamespace(family=DENSE),
                                 peak=V5E, n_devices=1, trace=trace,
                                 max_slots=max_slots,
                                 traced_calls=lambda: (calls_prefill, calls_decode))


def _reader(name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, "bench", "metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_padding_earns_nothing():
    """The readers count the real tokens the probe saw; a wider engine
    (more slots, a wider table) reads the same work."""
    pre = [PrefillCall(0, 1, (300, 40), (0, 512))]
    dec = [DecodeCall(1, 2, 8, (700, 90))]
    for name in ("mfu", "paged_decode_attn_roofline", "paged_prefill_attn_roofline"):
        read = _reader(name)
        narrow = read(_readings(STABLELM, pre, dec, max_slots=3))
        wide = read(_readings(STABLELM, pre, dec, max_slots=32))
        assert narrow == wide > 0
