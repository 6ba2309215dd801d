"""The system under test, built from a cell's files: the model from the
repository's registry (checked against the configuration file and its
family), the benchmark's own seeded weights, and a ``ServeFrontend`` over
a ``ServeEngine`` with the configuration's serving options."""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax

from bench import weights
from bench.spec import Cell

# configuration-file key -> how the registry's ArchConfig states it, for
# every family; a family's ARCH_KEYS add its own
_ARCH_KEYS = {
    "num_hidden_layers": lambda a: a.n_layers,
    "hidden_size": lambda a: a.d_model,
    "num_attention_heads": lambda a: a.n_heads,
    "num_key_value_heads": lambda a: a.n_kv_heads,
    "head_dim": lambda a: a.head_dim,
    "vocab_size": lambda a: a.vocab,
    "norm": lambda a: a.norm,
    "norm_eps": lambda a: a.norm_eps,
    "partial_rotary_factor": lambda a: a.rope_pct,
    "rope_theta": lambda a: a.rope_theta,
    "tie_word_embeddings": lambda a: a.tie_embeddings,
    "use_qkv_bias": lambda a: a.qkv_bias,
}


def arch(family, config: Dict[str, Any], arch_cfg=None):
    """The registry's ArchConfig for the file, refused where they differ
    or where the family does not cover it."""
    if arch_cfg is None:
        from repro.configs import get_arch

        arch_cfg = get_arch(config["arch"])
    for key, get in {**_ARCH_KEYS, **family.ARCH_KEYS}.items():
        if config.get(key) != get(arch_cfg):
            raise ValueError(f"{config['name']}: {key}={config.get(key)!r} in the "
                             f"configuration file, {get(arch_cfg)!r} in the registry")
    family.check_arch(arch_cfg)
    return arch_cfg


def build(cell: Cell, seed: int, arch_cfg=None) -> Tuple[Any, Any, Any]:
    """(params, engine, frontend) for one run of the cell."""
    from repro.models.model import Model
    from repro.serve import FrontendConfig, ServeConfig, ServeEngine, ServeFrontend
    from repro.serve.sampling import GREEDY

    cfg = arch(cell.family, cell.config, arch_cfg)
    serving = cell.config["serving"]
    if serving["sampler"] != "greedy" or serving.get("eos_id") is not None:
        raise ValueError("the comparison with the reference needs greedy tokens, no EOS")
    model = Model(cfg)  # exact mode
    params = weights.make(cell.family, cell.config, seed)
    want = jax.eval_shape(lambda: model.serving_params(model.init(jax.random.PRNGKey(0))))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    if jax.tree.structure(want) != jax.tree.structure(got) or jax.tree.leaves(want) != jax.tree.leaves(got):
        raise ValueError(f"{cell.config['name']}: bench weights do not match the "
                         "engine's serving parameter tree")
    eng = cell.workload["engine"]
    config = ServeConfig(
        max_slots=eng["max_slots"], max_len=eng["max_len"],
        kv_pool_blocks=eng["kv_pool_blocks"],
        chunk_steps=serving["chunk_steps"], sampler=GREEDY, seed=seed & 0x7FFFFFFF,
        astra_accounting=serving["astra_accounting"],
        kv_block_size=serving["kv_block_size"], prefix_cache=serving["prefix_cache"],
        prefill_chunk_tokens=serving["prefill_chunk_tokens"],
        attn_impl=serving["attn_impl"],
    )
    engine = ServeEngine(model, params, config)
    return params, engine, ServeFrontend(engine, FrontendConfig())
