"""Shared CLI flag surface for the serving stack.

One module owns three things the CLIs and the linter must agree on:

* the **registry** — ``FIELD_FLAGS`` maps every CLI-reachable config
  dataclass field (``ServeConfig`` / ``FrontendConfig`` /
  ``ModelOptions``) to its flag, and ``INTERNAL_FIELDS`` records, with a
  reason, the fields deliberately *not* exposed.  The ``config-surface``
  checker (``repro.analysis``) cross-references both against the actual
  dataclass definitions and the ``add_argument`` calls below, so a field
  added without a flag (or a flag whose field was renamed) fails lint;
* :func:`add_serve_flags` / :func:`validate_serve_flags` — the engine,
  plan, paged-KV, and traffic flags themselves, used by
  ``launch/serve.py`` (validation at the CLI, not deep inside the
  engine);
* :func:`check_choices` — reject unknown names in comma-list flags
  loudly (``benchmarks/run.py --only`` used to silently skip typos).
"""
from __future__ import annotations

import argparse
from typing import Iterable, Sequence

from repro.core.astra_layer import MODES
from repro.core.plan import PRESET_PLANS
from repro.models.transformer import ModelOptions

# ---------------------------------------------------------------- registry
# "Cls.field" -> the flag that reaches it.  Checked by config-surface.
FIELD_FLAGS = {
    "ServeConfig.max_slots": "--max-slots",
    "ServeConfig.chunk_steps": "--chunk-steps",
    "ServeConfig.sampler": "--temperature",  # (+ --top-k, same SamplerConfig)
    "ServeConfig.seed": "--seed",
    "ServeConfig.kv_block_size": "--kv-block-size",
    "ServeConfig.kv_pool_blocks": "--kv-pool-blocks",
    "ServeConfig.prefix_cache": "--no-prefix-cache",
    "ServeConfig.prefill_chunk_tokens": "--prefill-chunk-tokens",
    "ServeConfig.attn_impl": "--attn-impl",
    "ServeConfig.kv_quant": "--kv-quant",
    "ServeConfig.degraded_mode": "--no-degraded-mode",
    "FrontendConfig.max_queue_depth": "--max-queue",
    "FrontendConfig.queue_timeout_s": "--queue-timeout",
    "FrontendConfig.max_concurrency": "--max-concurrency",
    "FrontendConfig.default_deadline_s": "--deadline",
    "FrontendConfig.max_retries": "--max-retries",
    "FrontendConfig.retry_backoff_s": "--retry-backoff",
    "ModelOptions.plan": "--plan",
    "ModelOptions.attn_impl": "--attn-impl",
    "ModelOptions.kv_quant": "--kv-quant",
}
# "Cls.field" -> why it is deliberately not CLI-reachable.
INTERNAL_FIELDS = {
    "ServeConfig.max_len": "derived per run from prompt lengths + --gen "
                           "(or the trace's max length), never set directly",
    "ServeConfig.astra_accounting": "always on in the serving CLI; only "
                                    "unit tests opt out of the simulator",
    "ModelOptions.cc": "deprecated uniform-mode alias; --plan/--mode "
                       "construct an ExecutionPlan instead",
    "ModelOptions.use_rglru_kernel": "kernel-selection toggle for the "
                                     "parity tests; serving always uses "
                                     "the default path",
    "ModelOptions.remat": "training-memory knob; inference never remats",
    "ModelOptions.capacity_factor": "MoE train-time capacity; serving "
                                    "uses the checkpoint's routing as-is",
    "ModelOptions.z_loss": "training-only auxiliary loss weight",
}


def check_choices(ap: argparse.ArgumentParser, flag: str,
                  values: Iterable[str], valid: Sequence[str]) -> None:
    """``ap.error`` on any value outside ``valid`` — comma-list flags must
    reject typos loudly, not silently run nothing."""
    unknown = sorted(set(values) - set(valid))
    if unknown:
        ap.error(f"{flag}: unknown name(s): {', '.join(unknown)}; "
                 f"valid: {', '.join(valid)}")


# ------------------------------------------------------------------- flags
def add_serve_flags(ap: argparse.ArgumentParser) -> None:
    """Register the engine / plan / paged-KV / traffic flag surface."""
    ap.add_argument("--mode", default="int8", choices=list(MODES),
                    help="uniform execution mode (shorthand for --plan <mode>)")
    ap.add_argument("--plan", default="",
                    help="per-site execution plan: preset "
                         f"({', '.join(sorted(PRESET_PLANS))}), uniform mode, "
                         "or JSON glob rules; overrides --mode")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--chunk-steps", type=int, default=8,
                    help="fused decode steps per dispatch")
    ap.add_argument("--max-slots", type=int, default=0,
                    help="engine slots (0 = one per request, traffic: 4)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kv-block-size", type=int, default=16,
                    help="paged KV cache block size in tokens "
                         "(docs/SERVING.md); 0 = dense per-slot caches")
    ap.add_argument("--kv-pool-blocks", type=int, default=0,
                    help="physical KV pool capacity in blocks, incl. "
                         "scratch (docs/SERVING.md §Paged KV); 0 = auto "
                         "(slot floor + 2 slots of prefix-cache headroom)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable radix-tree prefix reuse (paged mode only)")
    ap.add_argument("--prefill-chunk-tokens", type=int, default=0,
                    help="chunked-prefill scheduler token budget per round "
                         "(docs/SERVING.md §Scheduling); 0 = blocking "
                         "full-prompt admission")
    ap.add_argument("--kv-quant", default="none",
                    help="paged KV pool storage dtype (docs/SERVING.md "
                         "§KV quantization): none = model dtype; int8 = "
                         "quantized blocks against calibrated per-KV-head "
                         "scales (requires --calibrate and a paged "
                         "--kv-block-size)")
    ap.add_argument("--attn-impl", default="naive",
                    help="attention implementation (docs/SERVING.md "
                         "§Decode-attention memory model): naive = jnp "
                         "einsum; flash = Pallas kernels (gather-free "
                         "streaming decode over the paged pool, flash "
                         "prefill; interpret mode chosen by backend: "
                         "compiled on TPU, interpreted — correct but "
                         "slow — elsewhere)")
    ap.add_argument("--max-queue", type=int, default=-1,
                    help="admission queue capacity (0 = no waiting room, "
                         "-1 = unbounded); overflow is rejected as "
                         "queue_full (open-loop replay only)")
    ap.add_argument("--queue-timeout", type=float, default=0.0,
                    help="reject requests waiting longer than this many "
                         "seconds (queue_timeout); 0 = wait forever "
                         "(open-loop replay only)")
    ap.add_argument("--max-concurrency", type=int, default=0,
                    help="most admitted requests in flight inside the "
                         "engine at once (open-loop replay only); 0 = the "
                         "engine's --max-slots")
    ap.add_argument("--no-degraded-mode", action="store_true",
                    help="disable the pool-pressure response ladder "
                         "(docs/SERVING.md §Fault tolerance); a stalled "
                         "admission round then wedges loudly instead of "
                         "flushing the prefix cache / shedding load")
    ap.add_argument("--deadline", type=float, default=0.0,
                    help="per-request end-to-end deadline in seconds; "
                         "waiting requests expire, in-flight ones are "
                         "cancelled mid-decode (deadline_exceeded); 0 = no "
                         "deadline (open-loop replay only)")
    ap.add_argument("--max-retries", type=int, default=0,
                    help="retry attempts granted to requests ending in a "
                         "retryable fault class (docs/SERVING.md §Fault "
                         "tolerance); 0 = no retry (open-loop replay only)")
    ap.add_argument("--retry-backoff", type=float, default=0.5,
                    help="base retry backoff in seconds; attempt k waits "
                         "min(base * 2^(k-1), 8 * base) on the replay clock "
                         "(open-loop replay only)")
    ap.add_argument("--fault-every", type=int, default=0,
                    help="inject one deterministic fault every N supervisor "
                         "steps (docs/SERVING.md §Fault tolerance); 0 = no "
                         "injection (open-loop replay only)")
    ap.add_argument("--fault-kinds", default="step_error,nonfinite_logits",
                    help="comma list of fault kinds the injector cycles "
                         "through: step_error, nonfinite_logits, "
                         "pool_pressure, slow_step")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for the injector's victim-slot choices "
                         "(deterministic given the seed)")


def validate_serve_flags(ap: argparse.ArgumentParser, args) -> None:
    """Validate the flag surface at the CLI, not deep inside the engine
    (the engine/frontend re-check their own invariants at construction)."""
    if args.kv_block_size < 0:
        ap.error(
            f"--kv-block-size: {args.kv_block_size} is negative; pass a "
            "positive block size (tokens per KV block, docs/SERVING.md) or "
            "0 for the dense per-slot layout"
        )
    if args.kv_pool_blocks < 0:
        ap.error(
            f"--kv-pool-blocks: {args.kv_pool_blocks} is negative; pass a "
            "pool capacity in blocks (docs/SERVING.md §Paged KV) or 0 for "
            "the automatic floor + prefix-cache headroom"
        )
    if args.kv_pool_blocks and args.kv_block_size == 0:
        ap.error(
            "--kv-pool-blocks only applies to the paged KV cache; it is "
            "meaningless with --kv-block-size 0 (dense layout has no pool)"
        )
    if args.no_prefix_cache and args.kv_block_size == 0:
        ap.error(
            "--no-prefix-cache only applies to the paged KV cache; it is "
            "meaningless with --kv-block-size 0 (dense layout has no "
            "prefix cache to disable)"
        )
    if args.no_degraded_mode and args.kv_block_size == 0:
        ap.error(
            "--no-degraded-mode only applies to the paged KV cache; the "
            "dense layout has no block pool, hence no pressure ladder to "
            "disable"
        )
    if args.prefill_chunk_tokens < 0:
        ap.error(
            f"--prefill-chunk-tokens: {args.prefill_chunk_tokens} is "
            "negative; pass a per-round token budget (docs/SERVING.md "
            "§Scheduling) or 0 for blocking full-prompt admission"
        )
    if args.attn_impl not in ModelOptions.ATTN_IMPLS:
        ap.error(
            f"--attn-impl: {args.attn_impl!r} unknown; valid: "
            f"{', '.join(ModelOptions.ATTN_IMPLS)} (flash routes decode "
            "through the gather-free paged-attention kernel where the "
            "plan keeps qk/pv exact)"
        )
    if args.kv_quant not in ModelOptions.KV_QUANTS:
        ap.error(
            f"--kv-quant: {args.kv_quant!r} unknown; valid: "
            f"{', '.join(ModelOptions.KV_QUANTS)} (int8 stores paged KV "
            "blocks quantized against calibrated per-KV-head scales, "
            "docs/SERVING.md §KV quantization)"
        )
    if args.kv_quant != "none" and args.kv_block_size == 0:
        ap.error(
            "--kv-quant int8 requires the paged KV layout; pass "
            "--kv-block-size > 0 (dense per-slot caches stay in model "
            "dtype)"
        )
    if args.kv_quant != "none" and not args.calibrate:
        ap.error(
            "--kv-quant int8 needs calibrated per-KV-head scales; add "
            "--calibrate so the PTQ pass bakes KV scales into the plan "
            "(docs/SERVING.md §KV quantization)"
        )
    # ---- open-loop replay flags (FrontendConfig + fault injection)
    if not args.traffic_trace:
        for flag, val, default in (
                ("--max-queue", args.max_queue, -1),
                ("--queue-timeout", args.queue_timeout, 0.0),
                ("--max-concurrency", args.max_concurrency, 0),
                ("--virtual-step", args.virtual_step, 0.0),
                ("--deadline", args.deadline, 0.0),
                ("--max-retries", args.max_retries, 0),
                ("--retry-backoff", args.retry_backoff, 0.5),
                ("--fault-every", args.fault_every, 0),
                ("--fault-kinds", args.fault_kinds,
                 "step_error,nonfinite_logits"),
                ("--fault-seed", args.fault_seed, 0)):
            if val != default:
                ap.error(f"{flag} only applies to open-loop replay; pass "
                         "--traffic-trace <file or spec> to select it")
        return
    from repro.serve.faults import FAULT_KINDS

    if args.deadline < 0:
        ap.error(f"--deadline: {args.deadline} is negative; pass an "
                 "end-to-end deadline in seconds > 0, or 0 to disable")
    if args.max_retries < 0:
        ap.error(f"--max-retries: {args.max_retries} is negative; pass the "
                 "retry attempts granted to retryable faults, or 0 to "
                 "disable retry")
    if args.retry_backoff < 0:
        ap.error(f"--retry-backoff: {args.retry_backoff} is negative; pass "
                 "a base backoff in seconds >= 0")
    if args.fault_every < 0:
        ap.error(f"--fault-every: {args.fault_every} is negative; pass an "
                 "injection period in supervisor steps, or 0 to disable")
    check_choices(ap, "--fault-kinds",
                  [k for k in args.fault_kinds.split(",") if k],
                  list(FAULT_KINDS))
    if args.max_queue < -1:
        ap.error(f"--max-queue: {args.max_queue} is invalid; pass a queue "
                 "capacity >= 0 (0 = no waiting room) or -1 for unbounded")
    if args.queue_timeout < 0:
        ap.error(f"--queue-timeout: {args.queue_timeout} is negative; pass "
                 "a timeout in seconds > 0, or 0 to disable")
    if args.max_concurrency < 0:
        ap.error(f"--max-concurrency: {args.max_concurrency} is negative; "
                 "pass an in-flight cap >= 1 (must not exceed --max-slots) "
                 "or 0 to inherit the engine's max_slots")
    if args.virtual_step < 0:
        ap.error(f"--virtual-step: {args.virtual_step} is negative; pass a "
                 "virtual round time in seconds > 0, or 0 for wall-clock "
                 "replay")
    if args.compare_exact:
        ap.error("--compare-exact is not supported with --traffic-trace "
                 "(the replay already checks streamed-vs-terminal parity)")
