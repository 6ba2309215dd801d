"""Continuous-batching serve engine correctness.

The load-bearing claims:

* mixed prompt lengths in ONE running batch reproduce per-request decoding
  exactly (greedy), on both prefill strategies (packed full-seq for pure
  attention stacks; masked scan for recurrent/sliding-window stacks);
* slots are reused: more requests than slots all complete correctly;
* the fused ``lax.scan`` decode loop is token-identical to the seed-style
  per-step dispatch loop across exact/int8/sc modes;
* sampling: temperature draws are reproducible, top-k stays in the top-k.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models.model import Model
from repro.models.transformer import ModelOptions
from repro.core.astra_layer import ComputeConfig
from repro.serve import (
    GREEDY, SamplerConfig, ServeConfig, ServeEngine, full_seq_packable,
    make_fused_decode, pack_prompts, packed_prefill, unfused_decode,
)
from repro.serve.sampling import sample_logits


def _model(arch, mode="exact", dtype="float32", **red):
    cfg = get_arch(arch).reduced(**red)
    cfg = dataclasses.replace(cfg, dtype=dtype)
    return Model(cfg, ModelOptions(cc=ComputeConfig(mode)))


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    shape = (cfg.n_codebooks,) if cfg.n_codebooks else ()
    return [rng.integers(0, cfg.vocab, shape + (l,), dtype=np.int32) for l in lens]


def _per_request_greedy(model, params, prompt, gen, max_len):
    """Seed-style oracle: prompt through decode steps, then greedy argmax."""
    p = jnp.asarray(prompt)[None]
    states = model.init_decode_state(1, max_len)
    decode = jax.jit(model.decode)
    s0 = p.shape[-1]
    logits = None
    for t in range(s0):
        logits, states = decode(params, p[..., t : t + 1], states, jnp.int32(t))
    out = []
    for t in range(s0, s0 + gen):
        # per-codebook greedy: logits [B, 1, V] or [B, 1, C, V]
        ids = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        tok = ids[..., None] if model.cfg.n_codebooks else ids[:, None]
        out.append(np.asarray(tok[0]))
        logits, states = decode(params, tok, states, jnp.int32(t))
    return np.concatenate(out, axis=-1)


# --------------------------------------------------------- mixed lengths
@pytest.mark.parametrize("arch", ["stablelm-1.6b", "recurrentgemma-2b"])
def test_mixed_lengths_match_per_request(arch, key):
    """16/32/64-style mixed prompts in one running batch == per-request."""
    model = _model(arch, **({"window": 8} if get_arch(arch).window else {}))
    params = model.init(key)
    lens = (6, 11, 16)
    prompts = _prompts(model.cfg, lens)
    max_len = max(lens) + 10
    eng = ServeEngine(model, params,
                      ServeConfig(max_slots=3, max_len=max_len, chunk_steps=4))
    outs = eng.generate_batch(prompts, max_new_tokens=8)
    for p, o in zip(prompts, outs):
        ref = _per_request_greedy(model, params, p, 8, max_len)
        np.testing.assert_array_equal(o.tokens, ref)


@pytest.mark.parametrize("arch", ["stablelm-1.6b", "recurrentgemma-2b"])
def test_mixed_lengths_match_per_request_paged(arch, key):
    """Paged variant of the continuous-batching parity claim: the block
    pool + block-table indirection must be invisible to outputs (see
    tests/test_prefix_cache.py for the prefix-reuse claims)."""
    model = _model(arch, **({"window": 8} if get_arch(arch).window else {}))
    params = model.init(key)
    lens = (6, 11, 16)
    prompts = _prompts(model.cfg, lens)
    max_len = max(lens) + 10
    eng = ServeEngine(model, params,
                      ServeConfig(max_slots=3, max_len=max_len, chunk_steps=4,
                                  kv_block_size=8))
    outs = eng.generate_batch(prompts, max_new_tokens=8)
    for p, o in zip(prompts, outs):
        ref = _per_request_greedy(model, params, p, 8, max_len)
        np.testing.assert_array_equal(o.tokens, ref)


def test_window_larger_than_max_len(key):
    """Ring window > pre-allocated max_len: prefill must take the scan
    path (the full-seq pass emits window-sized rings that would not fit
    the clamped slotted cache)."""
    model = _model("recurrentgemma-2b")  # reduced keeps window=32
    assert model.cfg.window == 32
    params = model.init(key)
    prompts = _prompts(model.cfg, (5, 8))
    eng = ServeEngine(model, params,
                      ServeConfig(max_slots=2, max_len=20, chunk_steps=4))
    assert eng._force_scan_prefill
    outs = eng.generate_batch(prompts, max_new_tokens=6)
    for p, o in zip(prompts, outs):
        ref = _per_request_greedy(model, params, p, 6, 20)
        np.testing.assert_array_equal(o.tokens, ref)


def test_prefill_strategy_selection():
    attn_cfg = get_arch("stablelm-1.6b").reduced()
    rec_cfg = get_arch("recurrentgemma-2b").reduced(window=8)
    assert full_seq_packable(attn_cfg, [3, 5, 7])  # pure attention: pad-safe
    assert not full_seq_packable(rec_cfg, [3, 5, 7])  # recurrent: masked scan
    assert full_seq_packable(rec_cfg, [5, 5, 5])  # equal lengths: no padding


def test_packed_prefill_matches_single(key):
    """Packed mixed-length prefill logits == each prompt prefilled alone."""
    model = _model("stablelm-1.6b")
    params = model.init(key)
    prompts = _prompts(model.cfg, (4, 9))
    tokens, lengths = pack_prompts(prompts, model.cfg)
    last, _ = packed_prefill(model, params, tokens, lengths, 16,
                             lengths_static=[4, 9])
    for i, p in enumerate(prompts):
        t1, l1 = pack_prompts([p], model.cfg)
        last1, _ = packed_prefill(model, params, t1, l1, 16,
                                  lengths_static=[p.shape[-1]])
        np.testing.assert_allclose(np.asarray(last[i]), np.asarray(last1[0]),
                                   rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------ slot reuse
def test_slot_reuse_more_requests_than_slots(key):
    model = _model("stablelm-1.6b")
    params = model.init(key)
    lens = (5, 9, 7, 12, 4, 10)
    prompts = _prompts(model.cfg, lens)
    eng = ServeEngine(model, params,
                      ServeConfig(max_slots=2, max_len=32, chunk_steps=3))
    outs = eng.generate_batch(prompts, max_new_tokens=6)
    assert len(outs) == len(prompts)
    for p, o in zip(prompts, outs):
        assert o.gen_len == 6
        ref = _per_request_greedy(model, params, p, 6, 32)
        np.testing.assert_array_equal(o.tokens, ref)


def test_staggered_budgets_leave_at_step_granularity(key):
    """Different gen budgets: early finishers free their slot mid-stream."""
    model = _model("stablelm-1.6b")
    params = model.init(key)
    prompts = _prompts(model.cfg, (5, 5, 5))
    eng = ServeEngine(model, params,
                      ServeConfig(max_slots=2, max_len=32, chunk_steps=8))
    ids = [eng.submit(p, g) for p, g in zip(prompts, (2, 7, 5))]
    by_id = {o.request_id: o for o in eng.run()}
    for rid, g, p in zip(ids, (2, 7, 5), prompts):
        o = by_id[rid]
        assert o.gen_len == g
        ref = _per_request_greedy(model, params, p, g, 32)
        np.testing.assert_array_equal(o.tokens, ref)


def test_eos_stops_early(key):
    model = _model("stablelm-1.6b")
    params = model.init(key)
    [prompt] = _prompts(model.cfg, (6,))
    ref = _per_request_greedy(model, params, prompt, 12, 32)
    eos = int(ref[3])  # force a hit mid-stream
    eng = ServeEngine(model, params, ServeConfig(max_slots=1, max_len=32))
    [out] = eng.generate_batch([prompt], max_new_tokens=12, eos_id=eos)
    assert out.gen_len <= 12
    assert out.tokens[-1] == eos
    assert eos not in out.tokens[:-1]
    # EOS truncated a fused chunk: timing must count delivered tokens only
    assert out.timing.mean_itl_s >= 0.0
    assert out.wall_time_s >= out.timing.ttft_s


# ------------------------------------------- gather-free decode kernel
@pytest.mark.parametrize("arch", ["stablelm-1.6b", "recurrentgemma-2b"])
def test_paged_kernel_matches_sdpa_engine(arch, key):
    """attn_impl="flash" streams KV blocks through the block table
    (kernels/paged_attention) instead of gathering the logical view;
    the engine outputs must be token-identical across both global and
    windowed-ring paged layouts."""
    model = _model(arch, **({"window": 8} if get_arch(arch).window else {}))
    params = model.init(key)
    prompts = _prompts(model.cfg, (6, 11, 16))
    outs = {}
    for impl in ("naive", "flash"):
        eng = ServeEngine(model, params,
                          ServeConfig(max_slots=3, max_len=26, chunk_steps=4,
                                      kv_block_size=8, attn_impl=impl))
        outs[impl] = eng.generate_batch(prompts, 8)
    for a, b in zip(outs["naive"], outs["flash"]):
        np.testing.assert_array_equal(a.tokens, b.tokens)


def test_lower_decode_chunk_is_the_dispatched_program(key):
    """``lower_decode_chunk`` lowers the chunk ``step`` dispatches: after a
    run it compiles against the engine's state, emits [slots, steps]
    tokens, and follows the engine's attention path."""
    model = _model("stablelm-1.6b")
    params = model.init(key)
    prompts = _prompts(model.cfg, (6, 11, 16))
    texts = {}
    for impl in ("naive", "flash"):
        eng = ServeEngine(model, params,
                          ServeConfig(max_slots=3, max_len=26, chunk_steps=4,
                                      kv_block_size=8, attn_impl=impl))
        eng.generate_batch(prompts, 8)
        lowered = eng.lower_decode_chunk()
        assert lowered.out_info[0].shape == (3, 4)
        assert eng.lower_decode_chunk(3).out_info[0].shape == (3, 3)
        texts[impl] = lowered.compile().as_text()
    assert texts["naive"] != texts["flash"]


def test_dense_kernel_matches_sdpa_engine(key):
    """Dense layout: the length-masked decode kernel (and the flash
    full-sequence prefill) must be invisible to outputs too."""
    model = _model("stablelm-1.6b")
    params = model.init(key)
    prompts = _prompts(model.cfg, (6, 11, 16))
    outs = {}
    for impl in ("naive", "flash"):
        eng = ServeEngine(model, params,
                          ServeConfig(max_slots=3, max_len=26, chunk_steps=4,
                                      attn_impl=impl))
        outs[impl] = eng.generate_batch(prompts, 8)
    for a, b in zip(outs["naive"], outs["flash"]):
        np.testing.assert_array_equal(a.tokens, b.tokens)


def test_chunked_prefill_with_kernel_matches_blocking(key):
    """Chunked suffix prefill under the streamed kernel: chunks resume at
    arbitrary in-block offsets, so this pins the causal paged-prefill
    kernel against blocking naive admission, token-identically."""
    model = _model("stablelm-1.6b")
    params = model.init(key)
    prompts = _prompts(model.cfg, (5, 19, 9))
    ref_eng = ServeEngine(model, params,
                          ServeConfig(max_slots=3, max_len=32, chunk_steps=4,
                                      kv_block_size=8))
    ref = ref_eng.generate_batch(prompts, 8)
    eng = ServeEngine(model, params,
                      ServeConfig(max_slots=3, max_len=32, chunk_steps=4,
                                  kv_block_size=8, attn_impl="flash",
                                  prefill_chunk_tokens=6))
    outs = eng.generate_batch(prompts, 8)
    for a, b in zip(ref, outs):
        np.testing.assert_array_equal(a.tokens, b.tokens)


def test_serve_config_rejects_unknown_attn_impl(key):
    model = _model("stablelm-1.6b")
    params = model.init(key)
    with pytest.raises(ValueError, match="attn_impl"):
        ServeEngine(model, params, ServeConfig(max_slots=1, max_len=8,
                                               attn_impl="fused"))


# ------------------------------------------------- fused vs per-step loop
@pytest.mark.parametrize("mode", ["exact", "int8", "sc"])
@pytest.mark.parametrize("sampler", [GREEDY, SamplerConfig(0.8, 5)],
                         ids=["greedy", "topk"])
def test_fused_scan_matches_dispatch_loop(mode, sampler, key):
    model = _model("stablelm-1.6b", mode=mode)
    params = Model(model.cfg, ModelOptions()).init(key)
    b, s0, steps = 3, 4, 6
    tok = jax.random.randint(key, (b, 1), 0, model.cfg.vocab, jnp.int32)
    pos = jnp.full((b,), s0, jnp.int32)
    states = model.init_decode_state(b, 24)
    fused = make_fused_decode(model)
    toks_f, _, _ = fused(params, tok, states, pos, key, steps=steps, sampler=sampler)
    toks_u, _, _ = unfused_decode(model, params, tok, states, pos, key, steps, sampler)
    np.testing.assert_array_equal(np.asarray(toks_f), np.asarray(toks_u))


def test_per_slot_positions_match_scalar(key):
    """pos as [B] vector with equal entries == the scalar-pos decode path."""
    model = _model("stablelm-1.6b")
    params = model.init(key)
    b = 2
    tok = jax.random.randint(key, (b, 1), 0, model.cfg.vocab, jnp.int32)
    states = model.init_decode_state(b, 16)
    lg_s, st_s = model.decode(params, tok, states, jnp.int32(3))
    lg_v, st_v = model.decode(params, tok, states, jnp.full((b,), 3, jnp.int32))
    np.testing.assert_allclose(np.asarray(lg_s), np.asarray(lg_v), rtol=1e-6)
    for a, c in zip(jax.tree.leaves(st_s), jax.tree.leaves(st_v)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


# -------------------------------------------------------------- sampling
def test_sample_logits_greedy_and_topk(key):
    logits = jnp.asarray([[0.1, 3.0, -1.0, 2.0, 0.0]])
    assert int(sample_logits(logits, GREEDY, key)[0]) == 1
    draws = {int(sample_logits(logits, SamplerConfig(1.0, 2), jax.random.fold_in(key, i))[0])
             for i in range(50)}
    assert draws <= {1, 3}  # top-2 of the distribution
    same = [int(sample_logits(logits, SamplerConfig(1.0, 0), key)[0]) for _ in range(3)]
    assert len(set(same)) == 1  # same key -> same draw


def test_submit_validates_budget(key):
    model = _model("stablelm-1.6b")
    params = model.init(key)
    eng = ServeEngine(model, params, ServeConfig(max_slots=1, max_len=8))
    with pytest.raises(ValueError):
        eng.submit(np.zeros(6, np.int32), 6)


# ------------------------------------------------------------------- e2e
@pytest.mark.slow
@pytest.mark.parametrize("arch", ["stablelm-1.6b", "qwen1.5-0.5b", "xlstm-125m",
                                  "musicgen-large", "granite-moe-1b-a400m",
                                  "llama-3.2-vision-90b"])
def test_engine_e2e_archs(arch, key):
    """Long-running: mixed lengths + slot reuse across architecture families."""
    model = _model(arch)
    params = model.init(key)
    lens = (4, 9, 6, 12)
    prompts = _prompts(model.cfg, lens)
    eng = ServeEngine(model, params,
                      ServeConfig(max_slots=2, max_len=32, chunk_steps=4))
    outs = eng.generate_batch(prompts, max_new_tokens=8)
    for p, o in zip(prompts, outs):
        assert o.gen_len == 8
        assert o.hardware is not None and o.hardware.energy_j > 0
        ref = _per_request_greedy(model, params, p, 8, 32)
        np.testing.assert_array_equal(o.tokens, ref)
