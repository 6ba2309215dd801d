"""Per-arch reduced-config smoke tests (assignment requirement).

Each assigned architecture instantiates a REDUCED config of the same family
and runs one forward/train step on CPU, asserting output shapes + no NaNs;
plus one decode step against a fresh serving state.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS, SHAPES, get_arch, shape_applicable
from repro.models.model import Model, input_specs
from repro.models.transformer import ModelOptions
from repro.optim import AdamWConfig, adamw_init, adamw_update

ARCH_IDS = list(ARCHS)


def _batch_for(cfg, b, s, key):
    if cfg.n_codebooks:
        tokens = jax.random.randint(key, (b, cfg.n_codebooks, s), 0, cfg.vocab)
    else:
        tokens = jax.random.randint(key, (b, s), 0, cfg.vocab)
    batch = {"tokens": tokens}
    if cfg.vision_tokens:
        batch["vision_embeds"] = jax.random.normal(
            key, (b, cfg.vision_tokens, cfg.d_model), jnp.bfloat16
        )
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_and_train_step(arch, key):
    cfg = get_arch(arch).reduced()
    model = Model(cfg, ModelOptions())
    params = model.init(key)
    batch = _batch_for(cfg, 2, 32, key)
    loss, metrics = model.loss(params, batch)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), f"{arch} loss not finite"

    # one full train step (grads + AdamW) must stay finite
    grads = jax.grad(lambda p: model.loss(p, batch)[0])(params)
    gleaves = jax.tree.leaves(grads)
    assert all(bool(jnp.all(jnp.isfinite(g.astype(jnp.float32)))) for g in gleaves), arch
    opt = adamw_init(params)
    params2, opt2, stats = adamw_update(params, grads, opt, AdamWConfig())
    assert bool(jnp.isfinite(stats["grad_norm"]))
    # params actually moved
    moved = any(
        float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))) > 0
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(params2))
    )
    assert moved, f"{arch}: AdamW produced no update"


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_step_shapes(arch, key):
    cfg = get_arch(arch).reduced()
    model = Model(cfg, ModelOptions())
    params = model.init(key)
    b, max_len = 2, 64
    states = model.init_decode_state(b, max_len)
    tok = _batch_for(cfg, b, 1, key)["tokens"]
    logits, states2 = model.decode(params, tok, states, jnp.int32(0))
    v = cfg.vocab
    if cfg.n_codebooks:
        assert logits.shape == (b, 1, cfg.n_codebooks, v)
    else:
        assert logits.shape == (b, 1, v)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32)))), arch
    # state tree structure preserved
    assert jax.tree_util.tree_structure(states) == jax.tree_util.tree_structure(states2)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_input_specs_cover_all_shapes(arch):
    cfg = get_arch(arch)
    for shape in SHAPES.values():
        ok, why = shape_applicable(cfg, shape)
        if not ok:
            assert shape.name == "long_500k" and not cfg.is_subquadratic
            continue
        specs = input_specs(cfg, shape)
        if shape.kind in ("train", "prefill"):
            t = specs["tokens"]
            assert t.shape[0] == shape.global_batch and t.shape[-1] == shape.seq_len
        else:
            assert specs["token"].shape[-1] == 1
            assert specs["pos"].shape == ()
            # decode state trees must be non-empty and finite-sized
            leaves = jax.tree.leaves(specs["states"])
            assert leaves, arch


def test_long_500k_applicability_matrix():
    long = SHAPES["long_500k"]
    runnable = {a for a in ARCH_IDS if shape_applicable(get_arch(a), long)[0]}
    assert runnable == {"recurrentgemma-2b", "xlstm-125m"}


def test_param_counts_in_band():
    """Analytic param counts must be in the advertised ballpark."""
    bands = {
        "stablelm-1.6b": (1.2e9, 2.2e9),
        "qwen1.5-110b": (95e9, 125e9),
        "qwen1.5-0.5b": (0.3e9, 0.7e9),
        "qwen2.5-32b": (28e9, 36e9),
        "recurrentgemma-2b": (2e9, 3.5e9),
        "xlstm-125m": (0.08e9, 0.2e9),
        "musicgen-large": (1.5e9, 2.6e9),
        "llama-3.2-vision-90b": (75e9, 100e9),
        "qwen3-moe-30b-a3b": (25e9, 34e9),
        "granite-moe-1b-a400m": (0.8e9, 1.6e9),
    }
    for arch, (lo, hi) in bands.items():
        n = get_arch(arch).param_count()
        assert lo <= n <= hi, f"{arch}: {n:.3e} outside [{lo:.1e}, {hi:.1e}]"
    # MoE actives
    assert get_arch("qwen3-moe-30b-a3b").active_param_count() < 5e9
    assert get_arch("granite-moe-1b-a400m").active_param_count() < 0.6e9


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_serving_params_keep_exact_logits(arch, key):
    """Serving params store the GEMM/expert weights and embedding tables in
    the compute dtype; every use casts them to it anyway, so exact-mode
    logits are bit-identical to the f32 params'."""
    cfg = get_arch(arch).reduced()
    model = Model(cfg, ModelOptions())
    params = model.init(key)
    served = model.serving_params(params)
    leaves = jax.tree_util.tree_flatten_with_path(served)[0]
    narrowed = [jax.tree_util.keystr(p) for p, a in leaves if a.dtype == jnp.bfloat16]
    assert sum(a.size for _, a in leaves if a.dtype == jnp.bfloat16) > 0.5 * sum(
        a.size for _, a in leaves)
    assert not any("router" in n or "norm" in n for n in narrowed), narrowed
    batch = _batch_for(cfg, 2, 16, key)
    ref, _ = model.prefill(params, batch)
    got, _ = model.prefill(served, batch)
    assert got.dtype == ref.dtype
    assert bool(jnp.array_equal(got, ref)), float(jnp.max(jnp.abs(got - ref)))
