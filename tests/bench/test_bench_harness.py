"""A whole run of the harness at reduced width on the CPU, its chip check
skipped: the result line, and ``correct`` coming out false when the timed
path is broken underneath.  The fp8 control fails the comparison."""
import json
import os
import sys
import time

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
sys.path.insert(0, ROOT)

from bench import harness, spec, system, tiny  # noqa: E402

CELL = "stablelm-1.6b.longdoc"
# long enough for the sample's 96 tokens on a loaded CPU
WINDOW_S = 5.0


@pytest.fixture(scope="module")
def tiny_cell(tmp_path_factory):
    root, arch = tiny.make_root(tmp_path_factory.mktemp("bench"), CELL)
    return spec.load_cell(CELL, root), arch


def _run(cell, arch, seed, build=None):
    return harness.run_cell(cell, seed, WINDOW_S, False, time.perf_counter(), require_tpu=False,
                            arch_cfg=arch, build=build)


def test_a_run_prints_the_result_line(tiny_cell, capsys):
    cell, arch = tiny_cell
    res = _run(cell, arch, 2**33 + 3)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(res)[-1] == "check"
    assert res["correct"] is True, res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"output_tok_s", "setup_s"}
    assert res["device"]["platform"] == "cpu"
    limits = cell.workload["check"]["limits"]
    assert {k: v["limit"] for k, v in res["check"].items() if k != "tokens_compared"} == limits
    json.dumps(res)
    err = capsys.readouterr().err
    assert "requests due in the window" in err and "programs made in the window" in err


def test_a_token_altered_where_produced_is_not_correct(tiny_cell):
    """The fused decode's tokens are altered on their way out, after the
    program fed back what it computed."""
    cell, arch = tiny_cell
    vocab = cell.config["vocab_size"]

    def broken(c, seed, a):
        params, engine, fe = system.build(c, seed, a)
        fused = engine._fused

        def altered(*args, **kw):
            toks, finite, carry = fused(*args, **kw)
            return (toks + 1) % vocab, finite, carry

        engine._fused = altered
        return params, engine, fe

    res = _run(cell, arch, 5, build=broken)
    assert res["correct"] is False
    gap = res["check"][next(iter(cell.workload["check"]["limits"]))]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_fp8_control_fails_the_comparison(tiny_cell, seed):
    """``bench/control.py``'s verdicts at reduced width: the program's own
    tokens come out correct, the fp8 control's first choices not.  Here
    the program reads a mean gap of at most 3.8e-4 and the fp8 control at
    least 1.2e-2 (CPU, seven seeds); the int8 control reads 7.7e-4 to
    2.3e-3, too near the program at this width to fail a limit, and is
    read on the chip at the cell's own size (PERF.md)."""
    cell, arch = tiny_cell
    _, run = harness.serve(cell, seed, WINDOW_S, False, time.perf_counter(), require_tpu=False,
                           arch_cfg=arch)
    program = harness.compare(cell, seed, run)
    control = harness.compare(cell, seed, run, "fp8")
    assert program["tokens_compared"]["value"] >= cell.workload["check"]["min_tokens"]
    assert harness.correct_of(program) and not harness.correct_of(control)
    assert control["mean_logit_gap"]["value"] > control["mean_logit_gap"]["limit"]
