"""Whether what the timed path served is correct: by how much a served
token's logit lies below the reference's best.

Under greedy decoding every served token was the program's own argmax,
so against an exact reference its gap is rounding only.  After the
window closes, a sample of the requests served in it (drawn from the
seed, the longest always in it) is re-run through the reference over
prompt + served tokens, and at each served position the gap
``max(ref) - ref[served]`` is read; the cell's file says which readings
of those gaps are compared and their limits: any of ``READINGS`` here or
of the configuration's family's (``bench/families/<model_type>.py``),
which may also read the named per-position columns its reference
forward returns beside the logits.  A control
reads, at the same positions, the gap of the token that the reference
with its GEMMs' operands in int8 or float8 (``reference.py``) puts
first.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from bench import reference


def sample(records: Sequence, seed: int, min_tokens: int, max_requests: int) -> List:
    """Served requests to compare: the longest, then others in an order
    drawn from the seed, until ``min_tokens`` served tokens are in."""
    done = [r for r in records if r.ok]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + r.gen, r.index))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([seed, 99]).permutation(len(rest))
    out, n = [longest], longest.gen
    for j in order:
        if n >= min_tokens or len(out) >= max_requests:
            break
        out.append(rest[j])
        n += rest[j].gen
    return out


def _batch(items: Sequence[Tuple[np.ndarray, np.ndarray]], seq_len: int, gen_len: int):
    """Pad (prompt, served) pairs to one shape: tokens [B, seq_len] and the
    positions whose logits predict each served token [B, gen_len]."""
    b = len(items)
    toks = np.zeros((b, seq_len), np.int32)
    pos = np.zeros((b, gen_len), np.int32)
    for j, (prompt, served) in enumerate(items):
        seq = np.concatenate([prompt, served[:-1]])
        toks[j, :len(seq)] = seq
        pos[j, :len(served)] = len(prompt) - 1 + np.arange(len(served))
    return toks, pos


def gaps(family, cfg: Dict, w, items: Sequence[Tuple[np.ndarray, np.ndarray]], seq_len: int,
         gen_len: int, batch: int, control: Optional[str] = None,
         columns: Optional[Dict[str, List[np.ndarray]]] = None) -> List[np.ndarray]:
    """Per item, the gap at each served position: of the served token, or
    with ``control`` (``"int8"`` or ``"fp8"``) of that control's first
    choice.  ``columns``, where given, receives per item the reference's
    family columns at the same positions."""
    out = []
    for i in range(0, len(items), batch):
        chunk = list(items[i:i + batch])
        chunk += [chunk[-1]] * (batch - len(chunk))  # one compiled shape
        toks, pos = _batch(chunk, seq_len, gen_len)
        cols = {}
        ref = np.asarray(reference.logits(family, cfg, w, toks, pos, columns=cols))
        pick = (np.argmax(np.asarray(reference.logits(family, cfg, w, toks, pos, control)), -1)
                if control else None)
        for j, (_, served) in enumerate(items[i:i + batch]):
            n = len(served)
            r = ref[j, :n]
            tok = pick[j, :n] if control else served
            out.append(r.max(-1) - r[np.arange(n), tok])
            if columns is not None:
                for name, c in cols.items():
                    columns.setdefault(name, []).append(np.asarray(c)[j, :n])
    return out


# a reading: f(gaps, columns) over every compared token, the family's
# columns concatenated alike
READINGS = {
    # the widest gap: one flipped token shows, and so does rounding noise
    "max_logit_gap": lambda g, cols: float(g.max()),
    # the mean over every compared token: steady from seed to seed
    "mean_logit_gap": lambda g, cols: float(g.mean()),
}


def readers(family) -> Dict[str, Callable]:
    """The readings a cell of the family may compare: these and its own."""
    return {**READINGS, **getattr(family, "READINGS", {})}


def readings(family, gaps: Sequence[np.ndarray],
             columns: Optional[Dict[str, List[np.ndarray]]] = None) -> Dict[str, float]:
    g = np.concatenate(gaps)
    cols = {name: np.concatenate(c) for name, c in (columns or {}).items()}
    return {name: f(g, cols) for name, f in readers(family).items()}
