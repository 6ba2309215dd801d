"""A configuration's family (``bench/families/<model_type>.py``) is found
by its ``model_type`` under the cell's root: the dense family gives the
weights and reference logits it gave before the equations moved into it,
bit for bit; a family planted in a scratch root serves a cell and has its
own reading judged; a missing family file, a family key that differs from
the registry and a reading no one defines are refused before any device
work."""
import dataclasses
import hashlib
import json
import os
import sys
import time

import jax
import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
sys.path.insert(0, ROOT)

from bench import harness, reference, spec, system, tiny, weights  # noqa: E402

CELL = "stablelm-1.6b.longdoc"
WINDOW_S = 5.0


def digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)} {a.dtype} {a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


# sha256 of the tiny stablelm weights and of the reference's logits on one
# fixed batch, recorded with the code as it stood before the dense
# equations moved into bench/families/stablelm.py (CPU)
BEFORE_THE_MOVE = {
    (5, "weights"): "2de943d0b972fa8608f3b1396276154653894855284a6f7cf81617e2e70c8220",
    (5, None): "0d0d27a897f3d2bfaea88e7c169598dc096f3075546d69850415c7b76c36c325",
    (5, "int8"): "9be8a26e46803b7e443b624e2b571bc626f520066c327eba5c9bf23da3802486",
    (5, "fp8"): "35a7c50703bddfe2e7edbc0b635dc6954dba2e30922ed6adfcfa143ca853d3f2",
    (2**33 + 7, "weights"): "8ae82f81c1053bbb733c41b372cc50bfee029f5d3af3b22bce05757220df93e2",
    (2**33 + 7, None): "b6917482766b41b3e1c818a062f5af1c2c9b2f4a477d8e59969d6e860b64c6d0",
    (2**33 + 7, "int8"): "238a3422ee346cf59a269b958399e2a7b11b775a3dc25906609ecfc857f46044",
    (2**33 + 7, "fp8"): "dc9ab274f9f73448847a2007292f11ef3bcf2f618b2471c89bd865c809a16b16",
}


@pytest.fixture(scope="module")
def dense_cell(tmp_path_factory):
    root, arch = tiny.make_root(tmp_path_factory.mktemp("bench"), CELL)
    return spec.load_cell(CELL, root), arch


@pytest.mark.parametrize("seed,what", list(BEFORE_THE_MOVE), ids=str)
def test_the_dense_family_keeps_every_bit(dense_cell, seed, what):
    cell, _ = dense_cell
    cfg = cell.config
    w = weights.make(cell.family, cfg, seed)
    if what == "weights":
        got = digest(w)
    else:
        toks = np.random.default_rng(0).integers(0, cfg["vocab_size"], (2, 24)).astype(np.int32)
        pos = np.stack([np.arange(6, 12), np.arange(18, 24)]).astype(np.int32)
        got = digest(reference.logits(cell.family, cfg, w, toks, pos, what))
    assert got == BEFORE_THE_MOVE[seed, what]


# the dense family's equations with the reference's top-two logit margin
# as a column, and a reading of it; USED shows which pieces ran
TOY = '''
from pathlib import Path

import jax

from bench import spec

dense = spec.load_family(Path(__file__).parents[2], {"name": "toy", "model_type": "stablelm"})
ARCH_KEYS = dict(dense.ARCH_KEYS)
check_arch, params_per_token, tiny = dense.check_arch, dense.params_per_token, dense.tiny
USED = set()


def tree(cfg, key):
    USED.add("tree")
    return dense.tree(cfg, key)


def forward(cfg, w, tokens, positions, control):
    USED.add("forward")
    logits, _ = dense.forward(cfg, w, tokens, positions, control)
    top2 = jax.lax.top_k(logits, 2)[0]
    return logits, {"top_margin": top2[..., 0] - top2[..., 1]}


READINGS = {"mean_top_margin": lambda g, cols: float(cols["top_margin"].mean())}
'''


def _edit(path, update):
    with open(path) as f:
        obj = json.load(f)
    update(obj)
    with open(path, "w") as f:
        json.dump(obj, f)


def _plant(root, model_type="toy", family=TOY, config=(), limits=()):
    """Write ``family``, where given, as ``bench/families/<model_type>.py``,
    point the cell's configuration at it and add ``limits`` to the cell's."""
    if family is not None:
        with open(root / "bench" / "families" / f"{model_type}.py", "w") as f:
            f.write(family)
    _edit(root / "bench" / "configs" / "stablelm-1.6b.json",
          lambda c: c.update(config, model_type=model_type))
    _edit(root / "bench" / "workloads" / f"{CELL}.json",
          lambda w: w["check"]["limits"].update(limits))


@pytest.fixture(scope="module")
def toy_run(tmp_path_factory):
    root, arch = tiny.make_root(tmp_path_factory.mktemp("bench"), CELL)
    _plant(root, limits={"mean_top_margin": 1e9})
    cell = spec.load_cell(CELL, root)
    _, run = harness.serve(cell, 3, WINDOW_S, False, time.perf_counter(), require_tpu=False,
                           arch_cfg=arch)
    return cell, run


@pytest.mark.parametrize("limit,correct", [(1e9, True), (0.0, False)], ids=["within", "beyond"])
def test_a_planted_family_serves_and_its_reading_is_judged(toy_run, limit, correct):
    cell, run = toy_run
    assert cell.family.__file__ == str(cell.root / "bench" / "families" / "toy.py")
    ck = dict(cell.workload["check"], limits=dict(cell.workload["check"]["limits"],
                                                  mean_top_margin=limit))
    numbers = harness.compare(dataclasses.replace(cell, workload=dict(cell.workload, check=ck)),
                              3, run)
    assert cell.family.USED == {"tree", "forward"}
    assert numbers["mean_top_margin"]["limit"] == limit
    assert numbers["mean_top_margin"]["value"] > 0
    assert numbers["mean_logit_gap"]["value"] <= numbers["mean_logit_gap"]["limit"]
    assert harness.correct_of(numbers) is correct


MOE_KEY = TOY + '''
ARCH_KEYS["num_local_experts"] = lambda a: a.moe.n_experts if a.moe else 0
'''

REFUSALS = {
    "missing_file": (dict(model_type="nosuch", family=None), FileNotFoundError,
                     r"bench/families/nosuch\.py"),
    "arch_key": (dict(family=MOE_KEY, config={"num_local_experts": 8}), ValueError,
                 r"num_local_experts=8 in the configuration file, 0 in the registry"),
    "unknown_reading": (dict(limits={"no_such_reading": 1.0}), ValueError,
                        r"no_such_reading.*bench/families/toy\.py"),
}


@pytest.mark.parametrize("case", list(REFUSALS))
def test_a_planted_family_is_refused(tmp_path, case):
    root, arch = tiny.make_root(tmp_path, CELL)
    plant, exc, match = REFUSALS[case]
    _plant(root, **plant)
    with pytest.raises(exc, match=match):
        cell = spec.load_cell(CELL, root)
        system.arch(cell.family, cell.config, arch)
