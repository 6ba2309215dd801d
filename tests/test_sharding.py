"""Sharding rule table resolved against the production mesh (abstractly —
tests run on 1 CPU device; AbstractMesh carries only the axis geometry).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh

from repro.configs import get_arch
from repro.models.model import Model, input_specs
from repro.models.transformer import ModelOptions
from repro.configs.base import SHAPES
from repro.parallel.sharding import batch_specs, param_specs, state_specs

def _mesh(*pairs):
    """AbstractMesh across jax versions: <=0.5 takes ((name, size), ...)
    pairs; newer jax takes (axis_sizes, axis_names)."""
    try:
        return AbstractMesh(tuple(pairs))
    except TypeError:
        return AbstractMesh(tuple(s for _, s in pairs), tuple(n for n, _ in pairs))


MESH = _mesh(("data", 16), ("model", 16))
MESH3 = _mesh(("pod", 2), ("data", 16), ("model", 16))


def _spec_of(sharding):
    return tuple(sharding.spec)


def _flat(tree):
    return jax.tree_util.tree_flatten_with_path(tree)[0]


@pytest.mark.parametrize("arch", ["qwen1.5-110b", "qwen3-moe-30b-a3b", "recurrentgemma-2b", "xlstm-125m"])
def test_param_specs_divisible(arch):
    """Every sharded dim must be divisible by its mesh axes — the _guard
    contract; violations would fail at jit time on the pod."""
    cfg = get_arch(arch)
    shapes = Model(cfg, ModelOptions()).param_shapes()
    specs = param_specs(shapes, MESH)
    n_sharded = 0
    for (path, leaf), (_, sh) in zip(_flat(shapes), _flat(specs)):
        spec = _spec_of(sh)
        for dim, entry in zip(leaf.shape[-len(spec):] if spec else (), spec):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            size = int(np.prod([MESH.shape[a] for a in axes]))
            assert dim % size == 0, (path, leaf.shape, spec)
            n_sharded += 1
    assert n_sharded > 0, f"{arch}: no parameter is sharded at all"


def test_qwen110b_fits_per_device_budget():
    """FSDP+TP must bring the fp32 train state under the v5e HBM budget."""
    cfg = get_arch("qwen1.5-110b")
    shapes = Model(cfg, ModelOptions()).param_shapes()
    specs = param_specs(shapes, MESH)
    per_dev = 0
    for (_, leaf), (_, sh) in zip(_flat(shapes), _flat(specs)):
        n_shards = 1
        for dim, entry in zip(leaf.shape, (None,) * (len(leaf.shape) - len(_spec_of(sh))) + _spec_of(sh)):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            n_shards *= int(np.prod([MESH.shape[a] for a in axes]))
        per_dev += int(np.prod(leaf.shape)) // n_shards
    # params + grads + adam m/v in fp32 = 16 bytes per param-element
    assert per_dev * 16 < 16e9, f"{per_dev * 16 / 1e9:.1f} GB/device"


def test_batch_specs_use_all_dp_axes():
    cfg = get_arch("qwen1.5-0.5b")
    specs = input_specs(cfg, SHAPES["train_4k"])
    b2 = batch_specs(specs, MESH)
    assert _spec_of(b2["tokens"])[0] in (("data",), "data")
    b3 = batch_specs(specs, MESH3)
    assert _spec_of(b3["tokens"])[0] == ("pod", "data")


def test_batch_1_replicates():
    cfg = get_arch("recurrentgemma-2b")
    specs = input_specs(cfg, SHAPES["long_500k"])
    sh = batch_specs({"token": specs["token"]}, MESH)["token"]
    assert all(e is None for e in _spec_of(sh))  # batch 1: nothing to shard


def test_state_specs_kv_cache_layout():
    cfg = get_arch("qwen2.5-32b")  # kv=8: heads don't divide model=16
    specs = input_specs(cfg, SHAPES["decode_32k"])
    s_sh = state_specs(specs["states"], MESH, SHAPES["decode_32k"].global_batch)
    flat = _flat(s_sh)
    assert flat, "no decode state"
    for path, sh in flat:
        spec = _spec_of(sh)
        # batch axis sharded over data wherever present
        if len(spec) >= 2 and spec[0] is not None:
            assert spec[0] == ("data",) or spec[0] == "data"


def test_moe_expert_dim_sharded():
    cfg = get_arch("qwen3-moe-30b-a3b")
    shapes = Model(cfg, ModelOptions()).param_shapes()
    specs = param_specs(shapes, MESH)
    hits = [
        (_path(p), _spec_of(sh))
        for (p, leaf), (_, sh) in zip(_flat(shapes), _flat(specs))
        if "w_up" in _path(p) and "mlp" in _path(p)
    ]
    assert hits
    for path, spec in hits:
        assert "model" in str(spec), (path, spec)  # experts on the model axis


def _path(path):
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def _axes_named(spec):
    return [a for e in spec if e is not None for a in (e if isinstance(e, tuple) else (e,))]


def test_guard_names_each_mesh_axis_once():
    """No decode-state rule names a mesh axis in two dims (JAX rejects
    such a spec), in either KV layout."""
    for arch in ("qwen2.5-32b", "recurrentgemma-2b", "xlstm-125m"):
        cfg = get_arch(arch)
        states = input_specs(cfg, SHAPES["decode_32k"])["states"]
        for layout in ("heads", "seq"):
            for _, sh in _flat(state_specs(states, MESH3, 128, kv_layout=layout)):
                axes = _axes_named(_spec_of(sh))
                assert len(axes) == len(set(axes)), (arch, layout, _spec_of(sh))


@pytest.mark.parametrize("strategy", ["tp_fsdp", "fsdp", "ep_dp", "tp"])
def test_param_specs_never_repeat_an_axis(strategy):
    for arch in ("qwen1.5-0.5b", "qwen3-moe-30b-a3b", "recurrentgemma-2b"):
        shapes = Model(get_arch(arch), ModelOptions()).param_shapes()
        for _, sh in _flat(param_specs(shapes, MESH3, strategy)):
            axes = _axes_named(_spec_of(sh))
            assert len(axes) == len(set(axes)), (arch, _spec_of(sh))


# the logical layouts the model code passes to shard_act
_ACT_LAYOUTS = (
    ("batch", None, None),
    ("batch", None, "ffn"),
    ("batch", None, "rnn"),
    ("batch", "heads", None, None),
    ("batch", "experts", None, None),
)


@pytest.mark.parametrize("strategy", ["tp_fsdp", "fsdp", "ep_dp", "tp"])
def test_shard_act_names_each_mesh_axis_once(monkeypatch, strategy):
    """Every activation layout in use names each mesh axis at most once."""
    from repro.parallel import sharding

    monkeypatch.setattr(sharding.jax.lax, "with_sharding_constraint", lambda x, s: s)
    for logical in _ACT_LAYOUTS:
        x = jax.ShapeDtypeStruct((512, 64, 256, 128)[:len(logical)], jnp.float32)
        with sharding.activation_mesh(MESH3, strategy):
            spec = tuple(sharding.shard_act(x, logical).spec)
        axes = _axes_named(spec)
        assert axes and len(axes) == len(set(axes)), (logical, spec)
