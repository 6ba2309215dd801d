"""The serving program's spans (``repro.serve.tracing``) in a profiler
trace: every span of the table appears, nested under the engine round,
and its stats describe the work dispatched, with the served tokens the
same as an untraced run's."""
import dataclasses
import glob
import os

import jax
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models.model import Model
from repro.serve import FrontendConfig, ServeConfig, ServeEngine, ServeFrontend
from repro.serve import tracing
from repro.serve.scheduler import pow2_bucket

LENS = (5, 13, 9, 20)
GEN = 6
SPANS = (tracing.STEP, tracing.ADMIT, tracing.PREFILL_CHUNK, tracing.FIRST_TOKEN,
         tracing.DECODE_DISPATCH, tracing.HOST_SYNC, tracing.RETIRE,
         tracing.ACCOUNTING, tracing.FRONTEND_PUMP, tracing.FRONTEND_SUBMIT)


@pytest.fixture(scope="module")
def model_and_params():
    cfg = dataclasses.replace(get_arch("stablelm-1.6b").reduced(), dtype="float32")
    model = Model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _serve(model, params):
    """Four requests through a paged, chunked engine with two slots: the
    outputs (by request id), the engine, and the prefill plans and decode
    steps it dispatched."""
    engine = ServeEngine(model, params, ServeConfig(
        max_slots=2, max_len=32, kv_block_size=4, prefill_chunk_tokens=8,
        chunk_steps=4, seed=3))
    plans, decode_steps = [], []
    prefill, fused = engine._prefill_chunk_paged, engine._fused

    def record_prefill(plan):
        plans.append(list(plan))
        return prefill(plan)

    def record_decode(*args, steps: int, **kwargs):
        decode_steps.append(steps)
        return fused(*args, steps=steps, **kwargs)

    engine._prefill_chunk_paged, engine._fused = record_prefill, record_decode
    fe = ServeFrontend(engine, FrontendConfig())
    rng = np.random.default_rng(7)
    for n in LENS:
        fe.submit(rng.integers(0, model.cfg.vocab, n, dtype=np.int32), GEN)
    return {o.request_id: o for o in fe.run()}, engine, plans, decode_steps


def _spans(log_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("serve.", "frontend.")):
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                dict(e.stats)))
    return out


@pytest.fixture(scope="module")
def traced(model_and_params, tmp_path_factory):
    model, params = model_and_params
    plain = _serve(model, params)[0]
    log_dir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(log_dir)
    try:
        outs, engine, plans, steps = _serve(model, params)
    finally:
        jax.profiler.stop_trace()
    return plain, outs, engine, _spans(log_dir), plans, steps


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def test_every_span_appears_nested_under_the_round(traced):
    _, outs, engine, spans, plans, decode_steps = traced
    assert {s[0] for s in spans} == set(SPANS)
    rounds = _named(spans, tracing.STEP)
    assert [s[3]["step_num"] for s in rounds] == list(range(1, engine.stats()["step"] + 1))
    for name, t0, t1, _ in spans:
        if name.startswith("serve.") and name != tracing.STEP:
            assert any(s0 <= t0 and t1 <= s1 for _, s0, s1, _ in rounds), name
    # host syncs: each prefill and decode dispatch (either can wait for
    # device memory), each decode chunk's read and each first-token read
    n_first = len(_named(spans, tracing.FIRST_TOKEN))
    assert 1 <= n_first <= len(outs)
    assert len(_named(spans, tracing.HOST_SYNC)) == (
        len(plans) + 2 * len(decode_steps) + n_first)
    assert len(_named(spans, tracing.RETIRE)) == len(decode_steps)
    assert len(_named(spans, tracing.ACCOUNTING)) == len(outs)
    assert len(_named(spans, tracing.FRONTEND_SUBMIT)) == len(LENS)


def test_stats_add_up_to_the_work_served(traced):
    _, outs, engine, spans, _, decode_steps = traced
    chunks = _named(spans, tracing.PREFILL_CHUNK)
    decodes = _named(spans, tracing.DECODE_DISPATCH)
    assert sum(s[3]["tokens"] for s in chunks) == engine.scheduler_stats["prefill_tokens"]
    assert sum(s[3]["tokens"] for s in chunks) == sum(LENS)
    assert all(s[3]["rows"] * s[3]["width"] >= s[3]["tokens"] for s in chunks)
    assert [s[3]["steps"] for s in decodes] == decode_steps
    assert sum(o.gen_len for o in outs.values()) == GEN * len(LENS)


def test_prefill_chunk_stats_describe_the_dispatched_grid(traced):
    """Each chunk's rows and real tokens are its plan's, and its width is
    the pow2 bucket of the plan's longest row: what prefill_pad_pct
    divides."""
    _, _, engine, spans, plans, _ = traced
    chunks = _named(spans, tracing.PREFILL_CHUNK)
    assert len(chunks) == len(plans)
    for s, plan in zip(chunks, plans):
        longest = max(t for _, t in plan)
        assert s[3]["rows"] == len(plan)
        assert s[3]["tokens"] == sum(t for _, t in plan)
        assert s[3]["width"] == pow2_bucket(longest, engine.config.prefill_chunk_tokens)
        assert longest <= s[3]["width"] < 2 * longest


def test_traced_tokens_match_an_untraced_run(traced):
    plain, outs = traced[:2]
    assert sorted(plain) == sorted(outs)
    for rid, o in outs.items():
        np.testing.assert_array_equal(o.tokens, plain[rid].tokens)
