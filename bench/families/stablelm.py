"""The dense family: every layer global causal attention (GQA) and a
SiLU-gated MLP, pre-norm residual blocks, LayerNorm or RMSNorm, an untied
or tied LM head.  What this family adds to the shared pieces of
``bench/system.py``, ``weights.py``, ``reference.py``, ``work.py`` and
``tiny.py``; found by the configuration's ``model_type``."""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from bench import reference, weights

# configuration-file key -> how the registry's ArchConfig states it,
# beyond the keys every family shares (bench/system.py)
ARCH_KEYS = {
    "intermediate_size": lambda a: a.d_ff,
    "hidden_act": lambda a: {"swiglu": "silu"}.get(a.act, a.act),
}


def check_arch(a) -> None:
    """Refuse an ArchConfig whose layers this reference does not cover."""
    if a.logit_softcap or a.window or a.moe or set(a.layer_kinds) != {"attn"}:
        raise ValueError(f"{a.name}: the dense family covers global attention and "
                         "dense FFNs only")


def tree(cfg: Dict[str, Any], key) -> Dict[str, Any]:
    """The seeded weights in the engine's serving layout (stacked layers
    under ``units/slot0``): GEMM and embedding weights in bfloat16, norms
    in float32."""
    L, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    h, kvh, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    v, norm = cfg["vocab_size"], cfg["norm"]
    q_dim, kv_dim = h * hd, kvh * hd
    ks = iter(jax.random.split(key, 16))
    dense = lambda din, dout: {"w": weights.normal(next(ks), (L, din, dout), din ** -0.5,
                                                   weights.BF16)}
    layer = {
        "pre_norm": weights.norm(next(ks), L, d, norm),
        "post_norm": weights.norm(next(ks), L, d, norm),
        "core": {"wq": dense(d, q_dim), "wk": dense(d, kv_dim),
                 "wv": dense(d, kv_dim), "wo": dense(q_dim, d)},
    }
    f = cfg["intermediate_size"]
    layer["mlp"] = {"up": dense(d, f), "gate": dense(d, f), "down": dense(f, d)}
    return {
        "embedding": {"table": weights.normal(next(ks), (v, d), 0.02, weights.BF16)},
        "head": ({} if cfg["tie_word_embeddings"]
                 else {"w": weights.normal(next(ks), (d, v), 1 / math.sqrt(d), weights.BF16)}),
        "final_norm": weights.norm(next(ks), 0, d, norm),
        "units": {"slot0": layer},
    }


def _ffn(p, h, control):
    mm = reference.mm
    up, gate = mm(h, p["up"]["w"], control), mm(h, p["gate"]["w"], control)
    return mm(jax.nn.silu(gate) * up, p["down"]["w"], control)


def forward(cfg, w, tokens, positions, control):
    """Float32 logits ``[B, G, V]`` at ``positions``, and no columns."""
    norm = lambda p, x: reference.norm(p, x, cfg["norm"], cfg["norm_eps"])
    x = w["embedding"]["table"].astype(jnp.float32)[tokens]

    def layer(x, p):
        x = x + reference.attention(cfg, p["core"], norm(p["pre_norm"], x), control)
        x = x + _ffn(p["mlp"], norm(p["post_norm"], x), control)
        return x, None

    x, _ = jax.lax.scan(layer, x, w["units"]["slot0"])
    x = jnp.take_along_axis(x, positions[..., None], axis=1)  # [B, G, D]
    x = norm(w["final_norm"], x)
    head = (w["embedding"]["table"].T if cfg["tie_word_embeddings"]
            else w["head"]["w"])
    return reference.mm(x, head, control), {}


def params_per_token(cfg: Dict) -> int:
    """The MLP's weights a token multiplies by, over all layers."""
    return cfg["num_hidden_layers"] * 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def tiny(conf: Dict, a) -> Dict:
    """The keys ``tiny.make_root`` sets from the registry's ``reduced()``."""
    return {"intermediate_size": a.d_ff}
