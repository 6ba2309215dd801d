"""The plain reference against the engine at reduced width on the CPU;
a planted decode fault fails the comparison;
``bench/run.py`` refuses to run without a TPU."""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
sys.path.insert(0, ROOT)

from bench import check, reference, spec, system, tiny, weights  # noqa: E402

CELLS = ["stablelm-1.6b.longdoc"]
# the mean gap over the served tokens, as the cells compare it.  bf16
# serving against the f32 reference at reduced width (CPU, 16 requests)
# reads at most 6.4e-4; the fp8 control reads at least 2.8e-2 (three
# seeds)
GAP_LIMIT = {CELLS[0]: 0.004}


@pytest.fixture(scope="module", params=CELLS)
def tiny_cell(request, tmp_path_factory):
    root, arch = tiny.make_root(tmp_path_factory.mktemp("bench"), request.param)
    return spec.load_cell(request.param, root), arch


def _serve(cell, arch, seed, n=16):
    from bench import generator

    _, engine, _ = system.build(cell, seed, arch)
    reqs = generator.requests(cell.traffic, seed, n, cell.config["vocab_size"])
    ids = [engine.submit(p, g) for p, g in reqs]
    outs = {o.request_id: o for o in engine.run()}
    return [(p, np.asarray(outs[i].tokens)) for (p, _), i in zip(reqs, ids)]


def _gap(cell, seed, items):
    ck = cell.workload["check"]
    w = weights.make(cell.family, cell.config, seed)
    return check.readings(cell.family, check.gaps(cell.family, cell.config, w, items,
                                                  ck["seq_len"], ck["gen_len"],
                                                  ck["batch"]))["mean_logit_gap"]


def test_reference_matches_the_program_forward(tiny_cell):
    """Full-sequence logits of the program's exact forward (bf16, as the
    engine's prefill runs it) against the float32 reference, at every
    position."""
    from repro.models.model import Model
    from repro.serve.prefill import _drop_free

    cell, arch = tiny_cell
    w = weights.make(cell.family, cell.config, 7)
    toks = np.random.default_rng(7).integers(0, arch.vocab, (2, 40)).astype(np.int32)
    got, _ = _drop_free(Model(arch)).prefill(w, {"tokens": toks})
    pos = np.broadcast_to(np.arange(40), (2, 40))
    ref = np.asarray(reference.logits(cell.family, cell.config, w, toks, pos))
    err = np.linalg.norm(np.asarray(got) - ref) / np.linalg.norm(ref)
    assert err < 2e-2


def test_engine_prefill_then_decode_agrees_with_the_reference(tiny_cell):
    cell, arch = tiny_cell
    items = _serve(cell, arch, seed=11)
    assert all(len(t) > 1 for _, t in items)
    assert _gap(cell, 11, items) <= GAP_LIMIT[cell.name]


def _drop_newest_key(monkeypatch):
    import repro.kernels.paged_attention as pa

    real = pa.paged_attention_decode

    def planted(q, k, v, table, kv_len, *a, **kw):
        return real(q, k, v, table, kv_len - 1, *a, **kw)

    monkeypatch.setattr(pa, "paged_attention_decode", planted)


FAULTS = {CELLS[0]: _drop_newest_key}


def test_a_planted_decode_fault_fails(tiny_cell, monkeypatch):
    from repro.serve import decode_loop

    cell, arch = tiny_cell
    FAULTS[cell.name](monkeypatch)
    decode_loop.make_fused_decode.cache_clear()
    jax.clear_caches()
    try:
        items = _serve(cell, arch, seed=11)
    finally:
        monkeypatch.undo()
        decode_loop.make_fused_decode.cache_clear()
        jax.clear_caches()
    assert _gap(cell, 11, items) > GAP_LIMIT[cell.name]


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, os.path.join(ROOT, "bench", "run.py"),
                        "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                        "--trace", "0"], capture_output=True, text=True, env=env,
                       cwd=ROOT, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr and "'cpu'" in p.stderr
