"""The one traffic generator: a mix's data file in, requests and arrival
times out.

Sizes are drawn by stratified quantiles: every ``block`` consecutive
requests hold the prompt lengths (and the output lengths) at the
distribution's quantiles ``(i + 0.5) / block``, in one fixed order drawn
from the mix's ``order_seed``.  So the traffic repeats every ``block``
requests, every run offers the same work, and the run's seed draws only
the token ids (uniform over the vocabulary) and the weights.  A window that starts on a block boundary therefore holds
the same work in every run; seeds that changed the sizes in a window of
about ten long requests moved `output_tok_s` by up to 17% (PERF.md).

Mixes (``bench/traffic/<name>.json``):

* ``arrivals``: ``{"kind": "backlog"}`` (offline: the queue never runs
  dry; ``bench/driver.py``);
* ``prompt`` and ``output``: ``{"dist": "lognormal", "median", "sigma",
  "min", "max"}`` or ``{"dist": "uniform", "min", "max"}``;
* ``block``: requests per period; ``order_seed``: the order within it.
"""
from __future__ import annotations

from statistics import NormalDist
from typing import Dict, List, Tuple

import numpy as np

_PROMPT, _OUTPUT, _TOKENS = range(3)
_UNIT = NormalDist()


def _rng(seed: int, stream: int, index: int) -> np.random.Generator:
    return np.random.default_rng([seed, stream, index])


def quantiles(dist: Dict, k: int) -> np.ndarray:
    """The ``k`` stratum lengths of a length distribution, clipped."""
    u = (np.arange(k) + 0.5) / k
    if dist["dist"] == "lognormal":
        z = np.asarray([_UNIT.inv_cdf(x) for x in u])
        vals = dist["median"] * np.exp(dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        vals = dist["min"] + u * (dist["max"] - dist["min"])
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(vals), dist["min"], dist["max"]).astype(np.int64)


def _period(traffic: Dict, stream: int, values: np.ndarray) -> np.ndarray:
    return values[_rng(traffic["order_seed"], stream, 0).permutation(len(values))]


def sizes(traffic: Dict) -> List[Tuple[int, int]]:
    """One period of (prompt length, output length)."""
    k = traffic["block"]
    p = _period(traffic, _PROMPT, quantiles(traffic["prompt"], k))
    g = _period(traffic, _OUTPUT, quantiles(traffic["output"], k))
    return [(int(a), int(b)) for a, b in zip(p, g)]


def request(traffic: Dict, seed: int, i: int, vocab: int) -> Tuple[np.ndarray, int]:
    """Request ``i`` of the mix: (prompt token ids, output length)."""
    p_len, gen = sizes(traffic)[i % traffic["block"]]
    prompt = _rng(seed, _TOKENS, i).integers(0, vocab, p_len, dtype=np.int32)
    return prompt, gen


def requests(traffic: Dict, seed: int, n: int, vocab: int) -> List[Tuple[np.ndarray, int]]:
    return [request(traffic, seed, i, vocab) for i in range(n)]
