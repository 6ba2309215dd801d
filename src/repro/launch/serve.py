"""Serving CLI over the continuous-batching engine (``repro.serve``).

Inference is the paper's target workload: this driver admits a batch of
requests (uniform or mixed prompt lengths) into the slotted serve engine,
decodes them through the fused ``lax.scan`` loop, and reports measured
tok/s plus the *modeled* ASTRA chip latency/energy per request
(``core.simulator`` — the numbers Figs. 5/6 are built from), under any of
the three ASTRA numeric modes:

  exact — bf16 reference            (accuracy oracle)
  int8  — ASTRA expectation path    (deployable quantized fast path)
  sc    — bit-true 128-bit streams  (the paper's stochastic arithmetic)

Execution modes are selected per GEMM site via ``--plan`` (preset name,
uniform mode, or JSON glob rules over the shared execution/simulator site
registry — docs/PLANS.md); ``--mode`` remains as the uniform shorthand.
``--calibrate`` runs a PTQ calibration pass (per-site activation scales)
on a synthetic batch before serving.

Weights are held as ``Model.serving_params`` stores them: GEMM weights
and embedding tables in the model's compute dtype (bf16), which halves
them against the f32 init and leaves exact mode's outputs unchanged.

KV memory is paged by default (``--kv-block-size``, docs/SERVING.md):
attention KV lives in fixed-size pooled blocks with radix-tree prefix
reuse on pure global-attention stacks (``--no-prefix-cache`` disables the
reuse; ``--kv-block-size 0`` restores the dense per-slot layout).

``--prefill-chunk-tokens N`` turns on the chunked-prefill scheduler
(docs/SERVING.md §Scheduling): prompts are prefilled in bounded chunks
interleaved with decode chunks under a shared per-round token budget of
``N``, so admitting a long prompt never stalls in-flight decode for more
than one bounded dispatch (0 = blocking full-prompt admission).  Each
request reports measured queue wait / TTFT / inter-token latency next to
the modeled chip cost.

``--traffic-trace`` switches from one-shot batch serving to open-loop
trace replay through the admission-controlled front-end
(docs/SERVING.md §Traffic, SLOs, and backpressure): requests arrive on
the trace's schedule, ``--max-queue`` bounds the waiting line,
``--queue-timeout`` sheds stale waiters, and the run ends with the SLO
scorecard (p50/p95/p99 TTFT + ITL, rejection rate, goodput).  The trace
is either a JSON file written by ``repro.traffic`` or an inline spec
like ``chat:rate=4,n=32,seed=0`` (suites: chat, longdoc, agent, mixed).
``--virtual-step`` replays in deterministic virtual time instead of
wall time.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b --reduced \
      --batch 4 --prompt-len 32 --gen 16 --mode int8 --compare-exact
  PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b --reduced \
      --traffic-trace 'mixed:rate=8,n=32' --max-queue 16 --queue-timeout 2 \
      --max-slots 4 --virtual-step 0.05
  PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b --reduced \
      --prompt-mix 16,32,64 --batch 6 --gen 16 --temperature 0.8 --top-k 40
  PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b --reduced \
      --plan mixed --calibrate --batch 4 --gen 8
  PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b --reduced \
      --plan '{"*.qk|*.pv": "int8", "*_proj": "sc", "default": "exact"}'
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.core.astra_layer import MODES
from repro.core.energy import AstraChipConfig
from repro.core.plan import PRESET_PLANS, ExecutionPlan
from repro.launch.compile_cache import place_compile_cache
from repro.launch.flags import add_serve_flags, validate_serve_flags
from repro.models.model import Model
from repro.models.transformer import ModelOptions
from repro.serve import (
    GREEDY, SamplerConfig, ServeConfig, ServeEngine, make_fused_decode,
    packed_prefill,
)
from repro.serve.sampling import sample_next_token


def generate(model: Model, params, prompts: jax.Array, gen_len: int, max_len: int,
             sampler: SamplerConfig = GREEDY, key=None):
    """Uniform-length batch decode.  prompts [B, S0] (or [B, C, S0]).

    Kept as the simple entry point (packed prefill + one fused scan over
    all ``gen_len`` steps).  Returns (prompt+generated tokens, decode tok/s).
    """
    cfg = model.cfg
    prompts = jnp.asarray(prompts, jnp.int32)
    b = prompts.shape[0]
    s0 = prompts.shape[-1]
    if gen_len == 0:
        return prompts, 0.0
    if key is None:
        key = jax.random.PRNGKey(0)
    lengths = jnp.full((b,), s0, jnp.int32)
    last_logits, states = packed_prefill(
        model, params, prompts, lengths, max_len, lengths_static=[s0] * b
    )
    key, sub = jax.random.split(key)
    first = sample_next_token(last_logits, sampler, sub, cfg)  # [B,1] | [B,C,1]
    pieces = [prompts, first]
    tps = 0.0
    if gen_len > 1:
        fused = make_fused_decode(model)
        pos0 = jnp.full((b,), s0, jnp.int32)
        args = (params, first, states, pos0, key)
        kw = dict(steps=gen_len - 1, sampler=sampler)
        jax.block_until_ready(fused(*args, **kw))  # warm: compile outside t0
        t0 = time.time()
        toks, _, _ = fused(*args, **kw)
        jax.block_until_ready(toks)
        # count only the steps inside the timed window (the first token
        # came from prefill, before t0)
        tps = b * (gen_len - 1) / max(time.time() - t0, 1e-9)
        pieces.append(toks)
    return jnp.concatenate(pieces, axis=-1), tps


def _prompt_lengths(args) -> list:
    if args.prompt_mix:
        mix = [int(x) for x in args.prompt_mix.split(",")]
        return [mix[i % len(mix)] for i in range(args.batch)]
    return [args.prompt_len] * args.batch


def make_prompts(cfg, lengths, key):
    """Random in-vocab prompts of the given lengths, one fold of ``key`` each."""
    prompts = []
    for i, l in enumerate(lengths):
        k = jax.random.fold_in(key, i)
        shape = (cfg.n_codebooks, l) if cfg.n_codebooks else (l,)
        prompts.append(np.asarray(jax.random.randint(k, shape, 0, cfg.vocab)))
    return prompts


def run_engine(model, params, prompts, args, sampler):
    """Serve ``prompts`` as the CLI does: a warm-up engine, then a timed
    one.  Returns (outputs, timed tok/s, the timed engine)."""
    max_len = max(p.shape[-1] for p in prompts) + args.gen + 1
    cfg = ServeConfig(max_slots=args.max_slots or len(prompts), max_len=max_len,
                      chunk_steps=args.chunk_steps, sampler=sampler, seed=args.seed,
                      kv_block_size=args.kv_block_size,
                      kv_pool_blocks=args.kv_pool_blocks,
                      prefix_cache=not args.no_prefix_cache,
                      prefill_chunk_tokens=args.prefill_chunk_tokens,
                      attn_impl=args.attn_impl, kv_quant=args.kv_quant)
    # warm run on a throwaway engine: the jitted prefill/chunk programs are
    # memoized per model, so the timed run below measures serving, not XLA
    # compilation
    ServeEngine(model, params, cfg, chip=AstraChipConfig()).generate_batch(
        prompts, args.gen
    )
    engine = ServeEngine(model, params, cfg, chip=AstraChipConfig())
    t0 = time.time()
    outs = engine.generate_batch(prompts, args.gen)
    dt = max(time.time() - t0, 1e-9)
    return outs, sum(o.gen_len for o in outs) / dt, engine


def _parse_plan(ap: argparse.ArgumentParser, spec: str) -> ExecutionPlan:
    """Validate ``--plan`` at the CLI, not deep inside ComputeConfig."""
    try:
        return ExecutionPlan.from_spec(spec)
    except (ValueError, TypeError) as e:
        ap.error(
            f"--plan: {e}\n  presets: {', '.join(sorted(PRESET_PLANS))}\n"
            f"  uniform modes: {', '.join(MODES)}\n"
            "  or JSON rules, e.g. "
            '\'{"*.qk|*.pv": "int8", "*_proj": "sc", "default": "exact"}\''
        )


def _load_trace(ap: argparse.ArgumentParser, spec: str, cfg):
    """``--traffic-trace`` accepts a JSON trace file or an inline spec."""
    import os

    from repro.traffic import TrafficTrace, generate_trace, parse_trace_spec

    if os.path.exists(spec):
        return TrafficTrace.load(spec)
    try:
        kw = parse_trace_spec(spec)
    except ValueError as e:
        ap.error(f"--traffic-trace: {spec!r} is neither a file nor a valid "
                 f"spec: {e}")
    return generate_trace(vocab=cfg.vocab, n_codebooks=cfg.n_codebooks, **kw)


def _run_traffic(model, params, trace, args, sampler):
    """Open-loop replay: admission front-end + SLO scorecard."""
    from repro.serve import (
        EngineSupervisor, FrontendConfig, ServeFaultInjector, ServeFrontend,
    )
    from repro.traffic import SLOConfig, VirtualClock, evaluate, replay_trace, trace_max_len

    block = args.kv_block_size
    max_len = trace_max_len(trace)
    if block:
        max_len = -(-max_len // block) * block
    serve_cfg = ServeConfig(
        max_slots=args.max_slots or 4, max_len=max_len,
        chunk_steps=args.chunk_steps, sampler=sampler, seed=args.seed,
        kv_block_size=block, kv_pool_blocks=args.kv_pool_blocks,
        prefix_cache=not args.no_prefix_cache,
        prefill_chunk_tokens=args.prefill_chunk_tokens,
        attn_impl=args.attn_impl, kv_quant=args.kv_quant,
        degraded_mode=not args.no_degraded_mode)
    fe_cfg = FrontendConfig(
        max_queue_depth=None if args.max_queue < 0 else args.max_queue,
        queue_timeout_s=args.queue_timeout or None,
        max_concurrency=args.max_concurrency or None,
        default_deadline_s=args.deadline or None,
        max_retries=args.max_retries,
        retry_backoff_s=args.retry_backoff)
    virtual = args.virtual_step > 0

    def stack(force_virtual=False, inject=False):
        clk = VirtualClock() if (virtual or force_virtual) else None
        eng = ServeEngine(model, params, serve_cfg, chip=AstraChipConfig(),
                          clock=clk)
        sup = None
        if inject and args.fault_every > 0:
            # generous horizon: the schedule just needs to outlast the run
            inj = ServeFaultInjector.periodic(
                n_steps=100 * max(len(trace), 1) + args.fault_every,
                every=args.fault_every,
                kinds=[k for k in args.fault_kinds.split(",") if k],
                seed=args.fault_seed)
            sup = EngineSupervisor(eng, inj)
        elif args.fault_every > 0 or args.max_retries > 0 or args.deadline:
            sup = EngineSupervisor(eng)  # containment + audit, no injection
        return ServeFrontend(eng, fe_cfg, clock=clk, supervisor=sup)

    # warm pass on a throwaway stack in virtual time (no sleeps, no
    # faults): the jitted programs are memoized per model, so the replay
    # below measures serving, not XLA compilation
    replay_trace(stack(force_virtual=True), trace,
                 virtual_step_s=args.virtual_step or 0.05)
    frontend = stack(inject=True)
    result = replay_trace(frontend, trace,
                          virtual_step_s=args.virtual_step if virtual else None)
    slo = (SLOConfig(args.slo_ttft, args.slo_itl)
           if args.slo_ttft > 0 and args.slo_itl > 0 else None)
    m = evaluate(result.outputs, result.duration_s, slo,
                 offered_rps=trace.rate_rps)
    clock_kind = f"virtual step={args.virtual_step}s" if virtual else "wall"
    print(f"[traffic] {trace.suite} trace: {len(trace)} requests at "
          f"{trace.rate_rps:g} rps ({trace.arrival}), replayed in "
          f"{result.duration_s:.2f}s ({clock_kind})")
    print(f"  completed {m['n_completed']}/{m['n_offered']} "
          f"({m['completed_tok_s']:.1f} tok/s), rejected {m['n_rejected']} "
          f"{m['rejected_by_reason'] or ''}")
    st = result.stats
    print(f"  queue: p50 wait {m['queue_p50_s'] * 1e3:.1f} ms, high-water "
          f"depth {st['max_queue_depth']}"
          + (f" (cap {fe_cfg.max_queue_depth})"
             if fe_cfg.max_queue_depth is not None else ""))
    print(f"  TTFT p50/p95/p99: {m['ttft_p50_s'] * 1e3:.1f} / "
          f"{m['ttft_p95_s'] * 1e3:.1f} / {m['ttft_p99_s'] * 1e3:.1f} ms")
    print(f"  ITL  p50/p95/p99: {m['itl_p50_s'] * 1e3:.2f} / "
          f"{m['itl_p95_s'] * 1e3:.2f} / {m['itl_p99_s'] * 1e3:.2f} ms "
          f"(max {m['itl_max_s'] * 1e3:.2f} ms)")
    if slo is not None:
        print(f"  SLO (ttft<={slo.ttft_s}s, itl<={slo.itl_s}s): "
              f"{m['n_slo_met']}/{m['n_offered']} met "
              f"({m['slo_attainment']:.0%}), goodput {m['goodput_rps']:.2f} rps")
    if frontend.supervisor is not None:
        sup_st = frontend.supervisor.stats
        eng_st = frontend.engine.stats()
        print(f"  faults: {sup_st['faults_injected']} injected over "
              f"{sup_st['steps']} supervised steps, "
              f"{eng_st['n_quarantined']} quarantined / "
              f"{eng_st['n_cancelled']} cancelled / {eng_st['n_shed']} shed, "
              f"{st['retries']} retries, {sup_st['audits_run']} audits clean")
        if eng_st["degraded_transitions"]:
            path = " -> ".join(name for _, name in eng_st["degraded_transitions"])
            print(f"  degraded ladder: {path} (now {eng_st['degraded_level']})")
    return result.outputs


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--prompt-mix", default="",
                    help="comma list of prompt lengths cycled over the batch, "
                         "e.g. 16,32,64 (continuous batching handles the mix)")
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--calibrate", action="store_true",
                    help="run a PTQ calibration pass (per-site activation "
                         "scales) on a synthetic batch before serving")
    ap.add_argument("--compare-exact", action="store_true",
                    help="also run exact mode and report token agreement")
    ap.add_argument("--traffic-trace", default="",
                    help="open-loop replay instead of one-shot batch: a "
                         "trace JSON written by repro.traffic, or an inline "
                         "spec like 'chat:rate=4,n=32,seed=0' "
                         "(docs/SERVING.md §Traffic)")
    add_serve_flags(ap)  # engine / plan / paged-KV / frontend surface
    ap.add_argument("--virtual-step", type=float, default=0.0,
                    help="replay on a virtual clock, each engine round "
                         "costing this many virtual seconds (deterministic "
                         "latencies); 0 = wall-clock replay")
    ap.add_argument("--slo-ttft", type=float, default=0.0,
                    help="TTFT bound in seconds for the goodput line "
                         "(0 with --slo-itl 0 = percentiles only)")
    ap.add_argument("--slo-itl", type=float, default=0.0,
                    help="max inter-token-gap bound in seconds for the "
                         "goodput line")
    args = ap.parse_args(argv)
    validate_serve_flags(ap, args)
    place_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    key = jax.random.PRNGKey(args.seed)
    sampler = SamplerConfig(args.temperature, args.top_k)

    base_model = Model(cfg, ModelOptions())
    params = base_model.serving_params(base_model.init(key))
    lengths = _prompt_lengths(args)
    prompts = make_prompts(cfg, lengths, key)

    plan = _parse_plan(ap, args.plan) if args.plan else ExecutionPlan.from_spec(args.mode)
    plan_label = plan.name or args.plan or args.mode
    model = Model(cfg, ModelOptions(plan=plan))
    if args.calibrate:
        from repro.serve.prefill import pack_prompts

        cal_tokens, _ = pack_prompts(prompts, cfg)
        model = model.calibrate(params, {"tokens": cal_tokens})
        print(f"calibrated {len(model.plan.act_scales)} site activation scales"
              f" + {len(model.plan.kv_scales)} KV storage-site scales")
    if args.kv_quant != "none":
        # surface the engine's rejection reason at the flag that caused it
        # instead of a deep ValueError traceback (the engine re-raises the
        # same reason if constructed directly)
        from repro.serve.engine import kv_quant_reject_reason

        reason = kv_quant_reject_reason(model, args.kv_block_size)
        if reason is not None:
            ap.error(f"--kv-quant: {reason}")
    if args.traffic_trace:
        trace = _load_trace(ap, args.traffic_trace, cfg)
        return _run_traffic(model, params, trace, args, sampler)
    outs, tps, engine = run_engine(model, params, prompts, args, sampler)
    print(f"[{plan_label}] {len(outs)} requests (prompt lens {sorted(set(lengths))}), "
          f"{args.gen} new tokens each: {tps:.1f} tok/s")
    kv = engine.kv_stats
    if kv:
        line = (f"  kv pool: {kv['pool_blocks']} blocks x "
                f"{kv['block_size']} tok, {kv['kv_quant']} storage "
                f"({kv['bytes_per_block']} B/block, "
                f"{kv['pool_bytes'] / 1e6:.2f} MB)")
        if not kv["prefix_cache"]:
            line += f"; prefix cache off: {kv['prefix_cache_off_reason']}"
        print(line)
    prefix_stats = engine.prefix_stats
    if prefix_stats:
        print(f"  prefix cache: {prefix_stats['hits']} hits / "
              f"{prefix_stats['misses']} misses, "
              f"{prefix_stats['hit_tokens']} prompt tokens reused, "
              f"{prefix_stats['evictions']} evictions")
    sched = engine.scheduler_stats
    if sched.get("active"):
        print(f"  scheduler: budget {sched['token_budget']} tok/round, "
              f"{sched['prefill_chunks']} prefill chunks / "
              f"{sched['prefill_tokens']} tokens over {sched['rounds']} rounds "
              f"({sched['starved_rounds']} decode-saturated)")
    timings = [o.timing for o in outs if o.timing is not None]
    if timings:
        print(f"  latency: queue {np.mean([t.queue_time_s for t in timings]) * 1e3:.1f} ms avg, "
              f"TTFT {np.mean([t.ttft_s for t in timings]) * 1e3:.1f} ms avg, "
              f"ITL {np.mean([t.mean_itl_s for t in timings]) * 1e3:.2f} ms avg / "
              f"{max(t.max_itl_s for t in timings) * 1e3:.2f} ms max")
    site_energy: dict = {}
    for o in outs:
        hw = o.hardware
        print(f"  req {o.request_id}: prompt {o.prompt.shape[-1]:>4} gen {o.gen_len:>3} | "
              f"ASTRA latency {hw.latency_s * 1e6:.3f} us, energy {hw.energy_j * 1e3:.3f} mJ, "
              f"{hw.energy_per_mac_j * 1e12:.3f} pJ/MAC")
        for site, e in hw.energy_by_site:
            site_energy[site] = site_energy.get(site, 0.0) + e
    top = sorted(site_energy.items(), key=lambda kv: -kv[1])[:5]
    total = sum(site_energy.values()) or 1.0
    print("  energy by site (top 5): " + ", ".join(
        f"{s} {e / total * 100:.1f}%" for s, e in top))

    # compare against exact iff the *effective* plan quantizes anything
    # (--plan overrides --mode, so the gate must look at the plan)
    from repro.core.plan import model_sites

    all_exact = all(model.plan.resolve(s).mode == "exact" for s in model_sites(cfg))
    if args.compare_exact and not all_exact:
        outs_ref, _, _eng = run_engine(base_model, params, prompts, args, sampler)
        agree = np.mean([
            np.mean(o.tokens == r.tokens) for o, r in zip(outs, outs_ref)
        ])
        print(f"token agreement vs exact: {agree * 100:.2f}%")
    return outs


if __name__ == "__main__":
    main()
