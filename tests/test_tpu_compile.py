"""The serving path's Pallas kernels compile for a TPU v5e at stablelm-1.6b widths.

Interpret-mode tests (test_kernels.py) cannot see what the TPU compiler
refuses: block shapes off the (8, 128) tiling, int8 sublane packing,
VMEM overruns.  These compile the kernels that the serving engine's
flash path runs — paged decode and paged suffix prefill over bf16 and
int8 pools — plus flash attention at S = 512, with ``interpret=False``,
against a *described* ``v5e:2x2`` topology (no chip needed), and check
that the program holds the Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and test workers import
every test file.  All such compiles live in this one file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest

# stablelm-1.6b: 32 heads (kv 32) of 64; the serving CLI's block of 16;
# chip_smoke.py's batch of 8 with 512-token prompts and 32 new tokens
B, H, HD, BS = 8, 32, 64, 16
S = 512
W = -(-(S + 32 + 1) // BS)  # table width for max_len 545
N_BLOCKS = 1 + B * W + 2 * W  # the engine's auto pool size


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _pool_args(dtype):
    pools = [((N_BLOCKS, H, BS, HD), dtype)] * 2
    return pools, ([((H,), jnp.float32)] * 2 if dtype == jnp.int8 else [])


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8], ids=["bf16", "int8"])
def test_paged_decode_compiles(one_chip, dtype):
    from repro.kernels.paged_attention import paged_attention_decode

    pools, scales = _pool_args(dtype)
    _compile(functools.partial(paged_attention_decode, interpret=False), one_chip,
             ((B, H, HD), jnp.bfloat16), *pools, ((B, W), jnp.int32), ((B,), jnp.int32),
             *scales)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8], ids=["bf16", "int8"])
def test_paged_prefill_compiles(one_chip, dtype):
    from repro.kernels.paged_attention import paged_attention_prefill

    pools, scales = _pool_args(dtype)
    ctx = 32  # the engine's pow2 context bucket for a 512-token suffix
    _compile(functools.partial(paged_attention_prefill, interpret=False), one_chip,
             ((B, H, S, HD), jnp.bfloat16), *pools, ((B, ctx), jnp.int32), ((B,), jnp.int32),
             *scales)


def test_flash_attention_compiles(one_chip):
    from repro.kernels.flash_attention import flash_attention

    qkv = ((1, H, S, HD), jnp.bfloat16)
    compiled = _compile(functools.partial(flash_attention, interpret=False), one_chip,
                        qkv, qkv, qkv)
    # q/k/v tiles plus the f32 carry stay far inside the 16 MiB of VMEM
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20
