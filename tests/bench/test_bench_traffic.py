"""The traffic generator and the backlog driver: seeded, stratified,
inside their clips; a lead-in of whole periods; a new workload file is
found by name with no code edit."""
import json
import os
import shutil
import sys
import time
import types

import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
sys.path.insert(0, ROOT)

from bench import generator, spec  # noqa: E402
from bench.driver import Driver  # noqa: E402


def mix(name):
    with open(os.path.join(ROOT, "bench", "traffic", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["longdoc"])
def test_the_seed_draws_the_tokens_and_the_mix_the_sizes(name):
    t = mix(name)
    a = generator.requests(t, 2**33 + 17, 40, 1000)
    b = generator.requests(t, 2**33 + 17, 40, 1000)
    c = generator.requests(t, 2**33 + 18, 40, 1000)
    assert all(np.array_equal(p, q) and g == h for (p, g), (q, h) in zip(a, b))
    assert all(len(p) == len(q) and g == h for (p, g), (q, h) in zip(a, c))
    assert not any(np.array_equal(p, q) for (p, _), (q, _) in zip(a, c))
    # request i does not depend on how many were drawn
    assert np.array_equal(generator.requests(t, 5, 3, 1000)[2][0], generator.requests(t, 5, 9, 1000)[2][0])


@pytest.mark.parametrize("name", ["longdoc"])
def test_lengths_stay_inside_clips_and_every_period_holds_the_quantiles(name):
    t = mix(name)
    k = t["block"]
    reqs = generator.requests(t, 123456789012, 3 * k, 50)
    p = np.array([len(x) for x, _ in reqs])
    g = np.array([n for _, n in reqs])
    assert p.min() >= t["prompt"]["min"] and p.max() <= t["prompt"]["max"]
    assert g.min() >= t["output"]["min"] and g.max() <= t["output"]["max"]
    assert sorted(p[:k]) == sorted(generator.quantiles(t["prompt"], k))
    assert sorted(g[:k]) == sorted(generator.quantiles(t["output"], k))
    assert np.array_equal(p[:k], p[k:2 * k]) and np.array_equal(g[:k], g[2 * k:])
    assert not np.array_equal(p[:k], np.sort(p[:k]))  # a drawn order, not sorted


def test_lognormal_median():
    t = mix("longdoc")
    q = generator.quantiles(t["prompt"], 16)
    assert np.median(q) == pytest.approx(t["prompt"]["median"], rel=0.05)


class FakeFrontend:
    """Serves every queued request in one round of ``round_s``."""

    def __init__(self, round_s=0.01):
        self.engine = types.SimpleNamespace(config=types.SimpleNamespace(max_slots=2, chunk_steps=8),
                                            stats=lambda: {"slots_live": len(self._q)})
        self._q, self._out, self._next, self.round_s = [], [], 0, round_s

    @property
    def stats(self):
        return {"queue_depth": len(self._q)}

    def submit(self, prompt, gen, on_tokens):
        self._next += 1
        self._q.append((self._next, gen, on_tokens))
        return self._next

    def busy(self):
        return bool(self._q)

    def pump(self):
        time.sleep(self.round_s)
        for rid, gen, cb in self._q:
            cb(np.zeros(1, np.int32))
            cb(np.zeros(gen - 1, np.int32))
            self._out.append(types.SimpleNamespace(request_id=rid, reject_reason=None,
                                                   fault_reason=None))
        self._q = []

    def drain(self):
        out, self._out = self._out, []
        return out


def test_the_window_opens_after_a_lead_in_of_whole_periods():
    t = mix("longdoc")
    k = t["block"]
    run = Driver(FakeFrontend(), t, seed=3, vocab=100).run(2 * k, window_s=0.2, drain_s=1.0)
    due = run.due_in_window()
    assert due[0].index == 2 * k
    assert all(r.due < run.w0 for r in run.records[:2 * k])
    assert run.t0 <= run.w0 < run.w1 == run.w0 + 0.2
    with pytest.raises(ValueError, match="whole number of periods"):
        Driver(FakeFrontend(), t, seed=3, vocab=100).run(k + 1, window_s=0.2, drain_s=1.0)


def test_the_driver_refuses_arrivals_it_does_not_know():
    t = dict(mix("longdoc"), arrivals={"kind": "poisson"})
    with pytest.raises(ValueError, match="unknown arrivals"):
        Driver(FakeFrontend(), t, seed=3, vocab=100)


def test_backlog_driver_keeps_the_queue_full():
    t = mix("longdoc")
    d = Driver(FakeFrontend(), t, seed=3, vocab=100)
    run = d.run(t["block"], window_s=0.2, drain_s=1.0)
    assert len(run.due_in_window()) >= 2
    assert run.due_in_window()[0].index % t["block"] == 0
    assert all(r.ok and r.token_times[0] >= r.due for r in run.due_in_window())


def test_a_new_workload_file_is_found_with_no_code_edit(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(tmp_path / "bench" / "traffic" / "burst.json", "w") as f:
        json.dump(dict(mix("longdoc"), name="burst", block=4), f)
    with open(tmp_path / "bench" / "workloads" / "stablelm-1.6b.burst.json", "w") as f:
        json.dump({"engine": {"max_slots": 2, "max_len": 1280, "kv_pool_blocks": 161},
                   "lead_in_requests": 8}, f)
    bench["workloads"].append({"name": "stablelm-1.6b.burst", "config": "stablelm-1.6b",
                               "traffic": "burst", "chips": 1, "why": "a test cell"})
    bench["end_to_end"][0]["workloads"].append("stablelm-1.6b.burst")
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    cell = spec.load_cell("stablelm-1.6b.burst", tmp_path)
    assert cell.traffic["name"] == "burst" and cell.config["name"] == "stablelm-1.6b"
    assert cell.workload["lead_in_requests"] == 8 and cell.traffic["block"] == 4
    assert [m["name"] for m in cell.end_to_end] == [bench["end_to_end"][0]["name"], "setup_s"]
    assert cell.per_layer == []
    with pytest.raises(KeyError, match="unknown workload"):
        spec.load_cell("stablelm-1.6b.nothing", tmp_path)
