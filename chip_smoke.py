#!/usr/bin/env python3
"""Chip smoke test: the serving engine's main path on one TPU chip.

    python3 chip_smoke.py               # one chip: serve phases a, b, c
    python3 chip_smoke.py --four-chips  # four chips: sharded training only

One chip.  stablelm-1.6b at its published widths (24 layers, d_model 2048,
32 heads of 64, d_ff 5632, vocab 100,352), random weights from ``--seed``
held as the serving CLI holds them (``Model.serving_params``: GEMM
weights and embeddings in bf16).  Eight requests (prompt lengths cycling
128, 256, 512; 32 new tokens each; the CLI's defaults: paged KV in
blocks of 16, 8 fused decode steps per dispatch) are served through
``repro.launch.serve.run_engine`` — the serving CLI's engine path: a
warm-up engine, then a timed one — three times, with the flags

  a. ``--mode exact --attn-impl naive``
  b. ``--mode exact --attn-impl flash`` (compiled paged-attention kernels)
  c. ``--mode int8``                    (the CLI default)

Every request must finish with exactly 32 in-vocab tokens and no fault,
and phase b's fused decode program must hold a compiled Pallas kernel
(``tpu_custom_call``).  Two probes follow.  The logit probe runs the
engine's paged prefill and one decode step (``prefill_paged_suffix`` and
``Model.decode``) under a and b: b's logits must match a's within
``LOGIT_RTOL``.  The kernel probe runs phase b's two kernels alone at the
smoke's shapes against their f32 references (``ref.py``) on the chip,
within ``KERNEL_RTOL``.

Four chips.  Three ``tp_fsdp`` training steps of qwen1.5-0.5b at its
published widths through ``repro.launch.train.main`` on a (data 2,
model 2) mesh, then the same steps on one of those chips; each step's
loss must agree within ``LOSS_ATOL`` and its gradient norm within
``GNORM_RTOL``.

Times printed here are smoke numbers, not a benchmark.  The last line of
stdout is the JSON result; any failure exits non-zero without printing
it.  Where JAX finds no TPU the script exits non-zero, naming the
platform it found — it never falls back to the CPU.
"""
from __future__ import annotations

import argparse
import functools
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

SERVE_ARCH = "stablelm-1.6b"
PROMPT_LENS = (128, 256, 512)
N_REQUESTS = 8
GEN = 32
KV_BLOCK = 16
CHUNK_STEPS = 8
PHASES = (
    ("a", "exact", "naive"),
    ("b", "exact", "flash"),
    ("c", "int8", "naive"),
)
# Relative L2 error of phase b's logits against phase a's.  Activations
# and KV are bf16 (unit roundoff 2^-9 ~ 2e-3) and the two attention paths
# round at different points: the kernel casts the *unnormalized*
# probabilities to bf16 and normalizes its f32 accumulator at the end,
# the einsum path casts the normalized probabilities.  Over 24 layers the
# gap measured 1.6e-2 to 1.7e-2 on a v5e, and 1.9e-2 at these prompt
# lengths with stablelm cut to d_model 64 on the CPU, where a decode
# kernel that ignores the newest key read 6.2e-2 (PERF.md).  The margin
# is thin, so the kernel probe carries that fault.
LOGIT_RTOL = 5e-2
# Relative L2 error of phase b's kernels against their f32 references on
# random bf16 inputs at the smoke's shapes: the kernels round the
# unnormalized probabilities to bf16 (2.1e-3 and 1.9e-3 interpreted on
# the CPU at stablelm's head shapes); ignoring the newest key read 7.9e-2
# there (PERF.md).
KERNEL_RTOL = 1e-2

TRAIN_ARCH = "qwen1.5-0.5b"
TRAIN_STEPS = 3
TRAIN_BATCH = 8
TRAIN_SEQ = 512
# Per-step agreement of the 2x2 mesh with one chip.  The sharded step
# sums matmul partials and gradients in a different order and across an
# all-reduce, which moves bf16 roundings only: on a v5e the mean
# cross-entropy (about ln V ~ 12 at random init) differed by at most
# 2.5e-4 and the gradient norm by 8e-4 relative (PERF.md).  The limits
# are 8x and 6x those.  Steps taken on half the batch (a lost data
# shard) moved the gradient norm by 39% to 42% and the loss by 2.6e-3 to
# 1.3e-2, at reduced width on 4 CPU devices (PERF.md).
LOSS_ATOL = 2e-3
GNORM_RTOL = 5e-3

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class SmokeFailure(AssertionError):
    """A phase produced a wrong result."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def require_tpu(count: int):
    """The TPU devices, or exit non-zero naming the platform found."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found platform "
                 f"{devs[0].platform!r} ({len(devs)} device(s)). No fallback.")
    if len(devs) < count:
        sys.exit(f"chip_smoke: needs {count} TPU chips; JAX found {len(devs)}")
    return devs


class CompileLog:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == COMPILE_EVENT:
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def snapshot(self):
        return self.seconds, self.compiles, self.cache_hits


def peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def prompt_lengths():
    return [PROMPT_LENS[i % len(PROMPT_LENS)] for i in range(N_REQUESTS)]


def serve_flags(attn_impl: str, seed: int):
    """The serving CLI's flags for one phase (every other flag at its default)."""
    from repro.launch.flags import add_serve_flags

    ap = argparse.ArgumentParser()
    add_serve_flags(ap)
    args = ap.parse_args(["--kv-block-size", str(KV_BLOCK), "--chunk-steps", str(CHUNK_STEPS),
                          "--seed", str(seed), "--attn-impl", attn_impl])
    args.gen = GEN
    return args


def check_outputs(label: str, outs, vocab: int) -> None:
    import numpy as np

    check(len(outs) == N_REQUESTS,
          f"phase {label}: {len(outs)} outputs for {N_REQUESTS} requests")
    for o in outs:
        check(o.fault_reason is None and o.reject_reason is None,
              f"phase {label}: request {o.request_id} ended with "
              f"fault={o.fault_reason!r} reject={o.reject_reason!r}")
        check(o.gen_len == GEN,
              f"phase {label}: request {o.request_id} made {o.gen_len} tokens, not {GEN}")
        toks = np.asarray(o.tokens)
        check(bool(((toks >= 0) & (toks < vocab)).all()),
              f"phase {label}: request {o.request_id} has out-of-vocab tokens")


def serve_phase(label, model, params, prompts, attn_impl, seed, log, require_compiled):
    """Serve the requests through the CLI's engine path; returns the outputs.
    Phase b's fused decode program must hold a compiled Pallas kernel."""
    from repro.launch.serve import run_engine
    from repro.serve import SamplerConfig

    args = serve_flags(attn_impl, seed)
    c0, n0, h0 = log.snapshot()
    t0 = time.perf_counter()
    outs, tok_s, engine = run_engine(model, params, prompts, args,
                                     SamplerConfig(args.temperature, args.top_k))
    wall = time.perf_counter() - t0
    check_outputs(label, outs, model.cfg.vocab)
    c1, n1, h1 = log.snapshot()
    print(f"[phase {label}] plan={model.plan.name} attn={attn_impl}: {len(outs)}/{N_REQUESTS} "
          f"requests x {GEN} tokens ok; {n1 - n0} compiles {c1 - c0:.1f} s "
          f"({h1 - h0} cache hits); phase wall {wall:.1f} s (warm-up + timed run); "
          f"timed run {tok_s:.1f} tok/s (smoke, not a benchmark); "
          f"peak_bytes_in_use {peak_bytes()}", flush=True)
    if attn_impl == "flash":
        kernel = "tpu_custom_call" in engine.lower_decode_chunk().compile().as_text()
        print(f"[phase {label}] tpu_custom_call in the engine's fused decode program: "
              f"{kernel}", flush=True)
        check(kernel or not require_compiled,
              f"phase {label}: the fused decode program holds no tpu_custom_call "
              "(Pallas kernels not compiled)")
    return outs


def logit_probe(model, params, prompts, tok=None):
    """The engine's paged prefill plus one decode step, compiled ahead of
    time so the programs' HLO can be read.  ``tok`` is the decode input
    (default: the prefill's argmax).  Returns (prefill last logits,
    decode logits, decode input, whether both programs hold a Pallas
    TPU kernel)."""
    import jax
    import jax.numpy as jnp

    from repro.models.attention import BlockTables
    from repro.serve.prefill import pack_prompts, prefill_paged_suffix

    n = len(prompts)
    max_len = max(p.shape[-1] for p in prompts) + GEN + 1
    w = -(-max_len // KV_BLOCK)
    tokens, lengths = pack_prompts(prompts, model.cfg)
    table = jnp.arange(1, 1 + n * w, dtype=jnp.int32).reshape(n, w)  # block 0: scratch
    states = model.init_decode_state(n, max_len, paged=(1 + n * w, KV_BLOCK))
    starts = jnp.zeros((n,), jnp.int32)
    prefill = jax.jit(functools.partial(prefill_paged_suffix, model),
                      static_argnames=("ctx_blocks",))
    prefill = prefill.lower(params, tokens, lengths, states, table, starts,
                            ctx_blocks=w).compile()
    last, states = prefill(params, tokens, lengths, states, table, starts)
    if tok is None:
        tok = jnp.argmax(last, axis=-1).astype(jnp.int32)  # [n, 1]
    tables = BlockTables(table, jnp.int32(0))
    decode = jax.jit(model.decode).lower(params, tok, states, lengths, tables).compile()
    logits, _ = decode(params, tok, states, lengths, tables)
    kernels = all("tpu_custom_call" in c.as_text() for c in (prefill, decode))
    return last, logits, tok, kernels


def kernel_probe(cfg, lengths, seed: int = 0):
    """Phase b's attention kernels alone, at the smoke's shapes, against
    their f32 references on the same device: random bf16 queries and KV
    pool, block tables laid out as the engine lays them.  The decode
    query sees ``length + 1`` keys (its own is the newest); prefill rows
    past a prompt's length are left out.  Returns {kernel: (rel L2,
    whether the program holds a Pallas TPU kernel)}."""
    import jax
    import jax.numpy as jnp

    import repro.kernels.paged_attention as pa
    from repro.kernels.paged_attention.ref import paged_decode_ref, paged_prefill_ref

    n, s = len(lengths), max(lengths)
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    w = -(-(s + GEN + 1) // KV_BLOCK)
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    bf = jnp.bfloat16
    k_pool = jax.random.normal(ks[0], (1 + n * w, kvh, KV_BLOCK, hd), bf)
    v_pool = jax.random.normal(ks[1], k_pool.shape, bf)
    table = jnp.arange(1, 1 + n * w, dtype=jnp.int32).reshape(n, w)
    lens = jnp.asarray(lengths, jnp.int32)
    q1 = jax.random.normal(ks[2], (n, h, hd), bf)
    qs = jax.random.normal(ks[3], (n, h, s, hd), bf)
    start = jnp.zeros((n,), jnp.int32)
    rows = (jnp.arange(s)[None] < lens[:, None])[:, None, :, None]  # [n, 1, s, 1]
    runs = {
        "paged decode": (pa.paged_attention_decode, paged_decode_ref,
                         (q1, k_pool, v_pool, table, lens + 1), None),
        "paged prefill": (pa.paged_attention_prefill, paged_prefill_ref,
                          (qs, k_pool, v_pool, table, start), rows),
    }
    out = {}
    for name, (op, ref_fn, args, valid) in runs.items():
        compiled = jax.jit(op).lower(*args).compile()
        got = compiled(*args).astype(jnp.float32)
        with jax.default_matmul_precision("highest"):
            ref = ref_fn(*args)
        if valid is not None:
            got, ref = jnp.where(valid, got, 0.0), jnp.where(valid, ref, 0.0)
        out[name] = (rel_l2(ref, got), "tpu_custom_call" in compiled.as_text())
    return out


def rel_l2(ref, got) -> float:
    import numpy as np

    ref = np.asarray(ref, np.float64)
    got = np.asarray(got, np.float64)
    return float(np.linalg.norm(got - ref) / max(np.linalg.norm(ref), 1e-30))


def serve_config(reduced: bool = False, **overrides):
    from repro.configs import get_arch

    cfg = get_arch(SERVE_ARCH)
    return cfg.reduced(**overrides) if reduced else cfg


def serve_params(cfg, seed: int):
    """Random weights from ``seed`` as the serving CLI holds them, and the
    requests' prompts."""
    import jax

    from repro.launch.serve import make_prompts
    from repro.models.model import Model

    key = jax.random.PRNGKey(seed)
    model = Model(cfg)
    return model.serving_params(model.init(key)), make_prompts(cfg, prompt_lengths(), key)


def phase_models(cfg):
    from repro.models.model import Model
    from repro.models.transformer import ModelOptions

    return {label: Model(cfg, ModelOptions(plan=mode, attn_impl=attn_impl))
            for label, mode, attn_impl in PHASES}


def logit_readings(models, params, prompts):
    """The logit probe under phases a and b: ({what: (rel L2, limit)},
    whether phase b's programs hold a Pallas TPU kernel)."""
    import numpy as np

    readings, logits, tok = {}, {}, None
    for label in ("a", "b"):
        last, dec, tok, kernels = logit_probe(models[label], params, prompts, tok)
        logits[label] = (np.asarray(last), np.asarray(dec))
    for i, what in enumerate(("prefill last-token logits", "one-step decode logits")):
        a, b = logits["a"][i], logits["b"][i]
        check(bool(np.isfinite(a).all() and np.isfinite(b).all()), f"{what} not finite")
        readings[what] = (rel_l2(a, b), LOGIT_RTOL)
        print(f"[probe] {what} b vs a: rel L2 {readings[what][0]:.4e} (tol {LOGIT_RTOL:g}), "
              f"max abs diff {float(np.max(np.abs(a - b))):.3e}, "
              f"max |a| {float(np.max(np.abs(a))):.3e}, argmax agreement "
              f"{np.mean(a.argmax(-1) == b.argmax(-1)) * 100:.1f}%", flush=True)
    return readings, kernels


def kernel_readings(cfg, seed: int = 0):
    """The kernel probe: ({kernel: (rel L2, limit)}, whether every
    kernel program holds a Pallas TPU kernel)."""
    readings, kernels = {}, True
    for name, (err, has) in kernel_probe(cfg, prompt_lengths(), seed).items():
        readings[f"{name} kernel"] = (err, KERNEL_RTOL)
        kernels = kernels and has
        print(f"[probe] {name} kernel vs f32 reference: rel L2 {err:.4e} "
              f"(tol {KERNEL_RTOL:g}); tpu_custom_call {has}", flush=True)
    return readings, kernels


def check_readings(readings) -> None:
    for what, (err, tol) in readings.items():
        check(err <= tol, f"{what}: {err:.4e} over the limit {tol:g}")


def serve_smoke(seed: int = 0, reduced: bool = False, require_compiled: bool = True):
    """Phases a, b, c, then the logit and kernel probes."""
    import numpy as np

    cfg = serve_config(reduced)
    print(f"[serve] {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.n_heads} heads of {cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab}; "
          f"prompts {prompt_lengths()}, {GEN} new tokens each", flush=True)
    log = CompileLog()
    params, prompts = serve_params(cfg, seed)
    models = phase_models(cfg)
    outs = {label: serve_phase(label, models[label], params, prompts, attn_impl, seed, log,
                               require_compiled)
            for label, _, attn_impl in PHASES}
    agree = np.mean([np.mean(a.tokens == b.tokens) for a, b in zip(outs["a"], outs["b"])])
    print(f"[serve] greedy token agreement b vs a: {agree * 100:.2f}% "
          "(printed, not asserted: argmax ties under random weights)", flush=True)

    readings, kernels = logit_readings(models, params, prompts)
    more, more_kernels = kernel_readings(cfg, seed)
    readings.update(more)
    kernels = kernels and more_kernels
    print(f"[probe] tpu_custom_call in every phase b probe program: {kernels}", flush=True)
    check(kernels or not require_compiled,
          "phase b: a probe program holds no tpu_custom_call (Pallas kernels not compiled)")
    check_readings(readings)
    seconds, compiles, hits = log.snapshot()
    print(f"[serve] total: {compiles} compiles, {seconds:.1f} s compiling, "
          f"{hits} persistent-cache hits; peak_bytes_in_use {peak_bytes()}", flush=True)


def train_argv(reduced: bool):
    argv = ["--arch", TRAIN_ARCH, "--steps", str(TRAIN_STEPS),
            "--batch", str(TRAIN_BATCH), "--seq", str(TRAIN_SEQ),
            "--warmup", "1", "--log-every", "1"]
    return argv + (["--reduced"] if reduced else [])


def train_runs(reduced: bool = False):
    """Three tp_fsdp steps on a (data 2, model 2) mesh and on one chip.
    Returns {mesh: [(loss, grad norm) per step]}."""
    import jax

    from repro.launch import train
    from repro.launch.mesh import make_mesh

    runs = {}
    log = CompileLog()
    for name, shape in (("2x2", (2, 2)), ("1x1", (1, 1))):
        m = make_mesh(shape, ("data", "model"), devices=jax.devices()[:shape[0] * shape[1]])
        c0, n0, _ = log.snapshot()
        t0 = time.perf_counter()
        summary = train.main(train_argv(reduced), mesh=m)
        wall = time.perf_counter() - t0
        c1, n1, _ = log.snapshot()
        check(summary["restarts"] == 0, f"mesh {name}: {summary['restarts']} restarts")
        runs[name] = [(float(summary["metrics"][s]["loss"]),
                       float(summary["metrics"][s]["grad_norm"])) for s in range(TRAIN_STEPS)]
        del summary  # frees the trained state before the next run
        print(f"[train {name}] mesh {dict(m.shape)} on {m.devices.size} chip(s): "
              f"(loss, grad norm) per step {runs[name]}; {n1 - n0} compiles "
              f"{c1 - c0:.1f} s; wall {wall:.1f} s (smoke, not a benchmark); "
              f"peak_bytes_in_use {peak_bytes()}", flush=True)
    return runs


def train_readings(runs):
    """{what: (difference, limit)} of the 2x2 run against one chip, per step."""
    out = {}
    for s, ((l1, g1), (l2, g2)) in enumerate(zip(runs["1x1"], runs["2x2"])):
        check(all(math.isfinite(x) for x in (l1, g1, l2, g2)), f"step {s}: not finite")
        out[f"step {s} |loss diff|"] = (abs(l2 - l1), LOSS_ATOL)
        out[f"step {s} grad norm rel diff"] = (abs(g2 - g1) / max(abs(g1), 1e-30), GNORM_RTOL)
    for what, (d, tol) in out.items():
        print(f"[train] {what} 2x2 vs 1x1 = {d:.4e} (tol {tol:g})", flush=True)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-training comparison on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devs = require_tpu(4 if args.four_chips else 1)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import place_compile_cache

    print(f"[device] {devs[0].platform} {devs[0].device_kind} x {len(devs)}; "
          f"compile cache {place_compile_cache()}", flush=True)
    if args.four_chips:
        check_readings(train_readings(train_runs()))
    else:
        serve_smoke(seed=args.seed)
    print(json.dumps({"ok": True, "device": {"platform": devs[0].platform,
                                             "kind": devs[0].device_kind,
                                             "count": len(devs)}}))


if __name__ == "__main__":
    main()
