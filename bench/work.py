"""Operations and bytes the served work needs, counted from shapes and
the requests' real context lengths.

Attention is counted from live tokens only: K and V read once for each
token in context, plus q in and the output out, in the model's dtype.  The
padded table width, the kernel's grid and a prefill row's padding earn
nothing, so the count is the same whatever implements attention.  Model
FLOPs are 2 x (non-embedding parameters a token touches + the LM head)
per token processed, plus causal attention's 4 x layers x context x
q_dim.  Attention's projections and the LM head are counted here; the
configuration's family (``bench/families/<model_type>.py``) counts the
rest of the weights a token touches.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

ACT_BYTES = 2  # bf16 queries, outputs and KV


def _dims(cfg: Dict) -> Tuple[int, int, int, int]:
    L = cfg["num_hidden_layers"]
    q_dim = cfg["num_attention_heads"] * cfg["head_dim"]
    kv_dim = cfg["num_key_value_heads"] * cfg["head_dim"]
    return L, q_dim, kv_dim, cfg["hidden_size"]


def decode_attn(cfg: Dict, contexts: Iterable[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) of single-query attention, one entry per token
    decoded, each with the number of keys it attends to."""
    L, q_dim, kv_dim, _ = _dims(cfg)
    flops = nbytes = 0.0
    for ctx in contexts:
        flops += 4.0 * q_dim * ctx
        nbytes += (2.0 * ctx * kv_dim + 2.0 * q_dim) * ACT_BYTES
    return L * flops, L * nbytes


def prefill_attn(cfg: Dict, rows: Iterable[Tuple[int, int]]) -> Tuple[float, float]:
    """(FLOPs, bytes) of causal suffix attention over rows of
    ``(start, n)``: ``n`` prompt tokens at positions ``start..start+n-1``,
    each attending to every position up to its own."""
    L, q_dim, kv_dim, _ = _dims(cfg)
    flops = nbytes = 0.0
    for start, n in rows:
        keys = n * start + n * (n + 1) / 2.0  # sum over i < n of (start + i + 1)
        flops += 4.0 * q_dim * keys
        nbytes += (2.0 * (start + n) * kv_dim + 2.0 * n * q_dim) * ACT_BYTES
    return L * flops, L * nbytes


def params_per_token(family, cfg: Dict) -> float:
    """Non-embedding weights a token multiplies by, plus the LM head."""
    L, q_dim, kv_dim, d = _dims(cfg)
    attn = d * q_dim + 2 * d * kv_dim + q_dim * d
    return float(L * attn + family.params_per_token(cfg) + d * cfg["vocab_size"])


def model_flops(family, cfg: Dict, contexts: Iterable[int]) -> float:
    """Model FLOPs of the tokens processed, one entry per token with the
    number of keys it attends to (its own position included)."""
    L, q_dim, _, _ = _dims(cfg)
    per_tok = 2.0 * params_per_token(family, cfg)
    n = total = 0.0
    for ctx in contexts:
        n += 1
        total += 4.0 * L * ctx * q_dim
    return n * per_tok + total


def least_seconds(flops: float, nbytes: float, peak: Dict[str, float]) -> Tuple[float, str]:
    """The roofline's least time for the work, and which bound sets it."""
    t_c, t_m = flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
