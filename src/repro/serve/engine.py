"""Continuous-batching serve engine over the fused scan decode.

The engine owns a fixed grid of ``max_slots`` decode slots backed by one
pre-allocated slotted state pytree (``Model.init_decode_state``).  Requests
with different prompt lengths and generation budgets flow through it:

  queue -> [admit: claim a free slot]
        -> [prefill: full-prompt (blocking) or bounded chunks (scheduler)]
        -> [fused decode chunks: one XLA dispatch per chunk]
        -> [retire finished slots -> per-request ASTRA accounting + timing]

Admission and retirement happen between chunks; a chunk never runs past
the earliest-finishing active slot (``steps = min(chunk_steps,
min(remaining))``), so requests join and leave at step granularity and no
slot ever generates beyond its budget.  Slots decode at *different*
absolute positions inside one fused chunk — ``pos`` is a per-slot vector
threaded down to the attention cache writes (``models.attention``).

Inactive slots still ride through the batch (fixed shapes keep one
compiled program); whatever they compute is discarded, and admission
overwrites the slot's entire state before it is ever read.

**Prefill scheduling** comes in two modes (docs/SERVING.md §Scheduling):

* **blocking** (``prefill_chunk_tokens=0``) — admission runs the full
  packed prompt prefill before the next decode chunk; one long prompt
  stalls every active slot's token stream for the whole prefill.
* **chunked** (``prefill_chunk_tokens>0``) — admitted requests hold their
  slot in the ``PREFILLING`` state while their prompt is fed in bounded
  chunks interleaved with decode chunks (``serve/scheduler.py``: FCFS,
  decode priority, shared per-round token budget).  Dense layouts chunk
  through the windowed masked scan (``prefill.prefill_window``); paged
  pure-attention stacks chunk through ``prefill_paged_suffix`` — a
  partially-prefilled request is just a request whose resident prefix is
  its own earlier chunks.  Paged *stateful* stacks (recurrent/windowed)
  fall back to blocking admission: their decode state cannot be resumed
  from pooled blocks (same constraint as the prefix cache).

KV memory comes in two layouts (docs/SERVING.md):

* **dense** (``kv_block_size=0``) — one max-length cache per slot, the
  legacy layout;
* **paged** (``kv_block_size>0``) — attn/local KV lives in fixed-size
  blocks drawn from a global pool (``serve/kv_pool.py``) addressed through
  per-slot block tables, with a radix-tree **prefix cache**
  (``serve/prefix_tree.py``): a request whose prompt prefix matches
  interned blocks skips prefill for them (pure global-attention stacks),
  reuses the KV verbatim, and bills those tokens at zero modeled ASTRA
  cost.  Inactive slots' table rows point at the scratch block, so their
  ride-along writes land nowhere readable.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from functools import lru_cache
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.core.energy import AstraChipConfig
from repro.core.plan import validate_site_registry
from repro.models.attention import BlockTables
from repro.models.model import Model
from repro.serve.clock import resolve_clock
from repro.serve.accounting import (
    RequestHardwareReport, RequestTiming, request_hardware_report, request_timing,
)
from repro.serve.decode_loop import make_fused_decode
from repro.serve.faults import (
    CANCEL_CLASS, CANCELLED, FAULT_NONFINITE, FAULT_POOL_PRESSURE,
    FAULT_STEP_ERROR, FaultSpec, InjectedStepError, NonFiniteLogitsError,
)
from repro.serve.kv_pool import KVBlockPool
from repro.serve.prefill import (
    pack_prompts, packed_prefill, prefill_paged_suffix, prefill_window,
)
from repro.serve.prefix_tree import RadixPrefixTree
from repro.serve.sampling import GREEDY, SamplerConfig, sample_next_token
from repro.serve.scheduler import (
    DegradedLadder, SchedulerConfig, TokenBudgetScheduler, pow2_bucket,
)
from repro.serve.slots import SlotState, paged_scatter_states, scatter_states
from repro.serve import tracing

_paged_scatter = jax.jit(paged_scatter_states)


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    max_slots: int = 8
    max_len: int = 256  # pre-allocated per-slot state length
    chunk_steps: int = 8  # fused steps per dispatch (1 = per-step batching)
    sampler: SamplerConfig = GREEDY
    seed: int = 0
    astra_accounting: bool = True
    # paged KV cache (docs/SERVING.md): 0 keeps the dense per-slot layout;
    # >0 stores attn/local KV in blocks of this many positions
    kv_block_size: int = 0
    # physical pool blocks incl. scratch; 0 = auto (slot floor + 2 slots'
    # worth of prefix-cache headroom)
    kv_pool_blocks: int = 0
    # radix-tree prefix reuse (paged + pure global-attention stacks only)
    prefix_cache: bool = True
    # chunked-prefill scheduler (docs/SERVING.md §Scheduling): per-round
    # token budget shared between decode (priority) and prefill; 0 keeps
    # the blocking full-prompt admission
    prefill_chunk_tokens: int = 0
    # attention implementation (docs/SERVING.md §Decode-attention memory
    # model): "naive" = jnp einsum (gathered logical view on paged
    # layouts); "flash" = Pallas kernels — gather-free streaming decode /
    # suffix prefill over the block table, flash full-sequence prefill.
    # None inherits the model's own ModelOptions.attn_impl; a string
    # overrides it for this engine.
    attn_impl: Optional[str] = None
    # KV pool storage dtype (docs/SERVING.md §KV quantization): "none"
    # keeps pool blocks in model dtype; "int8" stores them quantized
    # against the plan's calibrated per-KV-head static scales (requires
    # the paged layout and a calibrated, KV-deterministic plan — the
    # engine raises ValueError otherwise instead of silently degrading).
    # None inherits ModelOptions.kv_quant; a string overrides it.
    kv_quant: Optional[str] = None
    # degraded-mode ladder (docs/SERVING.md §Fault tolerance): on repeated
    # paged-admission pool pressure the engine flushes the prefix tree,
    # then disables prefix admission, then sheds the queue head as a
    # terminal "pool_pressure" fault output.  False restores the old
    # fail-loud behaviour (RuntimeError when wedged).
    degraded_mode: bool = True


@dataclasses.dataclass
class Request:
    id: int
    prompt: np.ndarray  # [S] or [C, S] multi-codebook, int32
    max_new_tokens: int
    eos_id: Optional[int] = None
    t_submit: float = 0.0  # stamped by ServeEngine.submit — queue wait and
    # wall time are measured from here, not from admission

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.shape[-1])


@dataclasses.dataclass
class RequestOutput:
    request_id: int
    prompt: np.ndarray
    tokens: np.ndarray  # generated tokens [G] (or [C, G])
    wall_time_s: float  # submit -> completion, true end to end
    hardware: Optional[RequestHardwareReport] = None
    timing: Optional[RequestTiming] = None  # queue/TTFT/ITL breakdown
    # set by the admission front-end (serve/frontend.py) when the request
    # was refused instead of served: "queue_full" | "queue_timeout".
    # Rejected requests still get this terminal output — they never
    # silently vanish — with empty tokens and queue-wait-only timing.
    reject_reason: Optional[str] = None
    # set when the request was terminated by the fault layer instead of
    # completing: a fault class from serve/faults.py ("step_error" |
    # "nonfinite_logits" | "pool_pressure") or a client-intent reason
    # ("cancelled" | "deadline_exceeded").  ``tokens`` holds whatever was
    # generated (and streamed) before termination.
    fault_reason: Optional[str] = None

    @property
    def gen_len(self) -> int:
        return int(self.tokens.shape[-1])

    @property
    def full_sequence(self) -> np.ndarray:
        return np.concatenate([self.prompt, self.tokens], axis=-1)


@dataclasses.dataclass
class _Slot:
    req: Request
    state: SlotState
    pos: int = 0  # absolute position of the next decode write
    remaining: int = 0  # tokens still to generate
    filled: int = 0  # prompt tokens resident (prefix-cached or prefilled)
    generated: List[np.ndarray] = dataclasses.field(default_factory=list)
    cached: int = 0  # prompt tokens served from the prefix cache
    t_admit: float = 0.0
    t_first: float = 0.0
    # token-arrival events [(host_time, n_tokens)] — one per fused chunk
    events: List[Tuple[float, int]] = dataclasses.field(default_factory=list)


@lru_cache(maxsize=256)
def _check_site_registry(cfg) -> None:
    """Executed-GEMM-site <-> simulator-op cross-check, once per config."""
    validate_site_registry(cfg)


def _kv_deterministic(model: Model) -> bool:
    """Whether interned KV is a pure function of the token path.

    Prefix reuse replays blocks computed under an earlier batch packing,
    so every executed GEMM site must run exact or with a *static*
    (PTQ-calibrated) activation scale — dynamic per-tensor scales depend
    on what else was packed into the prefill, which would make outputs
    vary with admission history (DESIGN.md §Numerics and parity).
    """
    from repro.core.plan import model_sites

    for s in model_sites(model.cfg):
        cc = model.plan.resolve(s)
        if cc.mode != "exact" and cc.act_scale is None:
            return False
    return True


def kv_quant_reject_reason(model: Model, kv_block_size: int) -> Optional[str]:
    """Why ``kv_quant="int8"`` cannot run on this engine (None = legal).

    Shared between ``ServeEngine.__init__`` (which raises ``ValueError``
    with this reason) and the serving CLI (which surfaces it next to the
    flag that caused it).  The checks encode the KV-determinism
    discipline (docs/SERVING.md §KV quantization): pooled int8 blocks are
    replayed by the prefix cache, so their contents must be a pure
    function of the token path — static calibrated scales only.
    """
    if kv_block_size <= 0:
        return (
            "kv_quant='int8' requires the paged KV layout "
            "(kv_block_size > 0): dense per-slot caches stay in model "
            "dtype (docs/SERVING.md §KV quantization)"
        )
    if not _kv_deterministic(model):
        return (
            "kv_quant='int8' requires deterministic KV: every quantized "
            "GEMM site must carry a static calibrated act_scale — "
            "dynamic per-tensor scales would make pooled int8 blocks "
            "depend on admission history; run Model.calibrate or use an "
            "exact/static plan (docs/SERVING.md §KV quantization)"
        )
    from repro.core.plan import kv_sites

    missing = [s for s in kv_sites(model.cfg) if model.plan.kv_scale(s) is None]
    if missing:
        return (
            f"kv_quant='int8' needs calibrated KV scales but the plan "
            f"carries none for {missing[0]!r}"
            + (f" (+{len(missing) - 1} more site(s))" if len(missing) > 1 else "")
            + "; run Model.calibrate before enabling kv_quant"
        )
    return None


def _pool_bytes_per_block(states) -> int:
    """Storage bytes one physical block occupies summed across every
    layer's K+V pools (at the pools' actual dtype — int8 under
    ``kv_quant``).  Per-pool scale vectors are constants, not per-block
    storage, and are excluded."""
    from repro.models.attention import PagedKVCache, QuantPagedKVCache

    total = 0
    for node in jax.tree.leaves(
        states, is_leaf=lambda x: isinstance(x, (PagedKVCache, QuantPagedKVCache))
    ):
        if not isinstance(node, (PagedKVCache, QuantPagedKVCache)):
            continue
        for arr in (node.k, node.v):
            # units pools are [U, n_blocks, kv, bs, hd], remainder pools
            # [n_blocks, kv, bs, hd]
            n_blocks = arr.shape[1] if arr.ndim == 5 else arr.shape[0]
            total += arr.size * arr.dtype.itemsize // n_blocks
    return total


class ServeEngine:
    def __init__(self, model: Model, params, config: Optional[ServeConfig] = None,
                 chip: Optional[AstraChipConfig] = None, plan=None,
                 clock: Optional[Callable[[], float]] = None,
                 token_sink: Optional[Callable[[int, np.ndarray], None]] = None):
        """``plan`` (optional, any ``ExecutionPlan.from_spec`` form) selects
        the execution plan for this engine, overriding the model's own.

        ``clock`` (optional) replaces the ambient wall clock
        (:data:`repro.serve.clock.wall_clock`) for every timestamp the
        engine takes (submission, admission, token arrivals, completion) —
        the traffic replay harness injects a virtual clock here so latency
        trajectories are deterministic (docs/SERVING.md §Traffic).

        ``token_sink`` (optional) is the incremental drain path: called as
        ``sink(request_id, tokens)`` the moment generated tokens exist on
        the host — the first sampled token at admission, then one call per
        fused decode chunk (EOS-trimmed, so the concatenation of a
        request's sink calls is exactly its final ``RequestOutput.tokens``).
        Finished outputs still flow through the ``run()``/``step()`` outbox
        exactly once; the sink only adds early visibility.
        """
        # None sentinel, not a default instance: a module-level default
        # would be one shared (frozen, but identity-bearing) object across
        # every engine — the B006 discipline the lint baseline enforces
        config = ServeConfig() if config is None else config
        if plan is not None:
            model = model.with_plan(plan)
        if (config.attn_impl is not None
                and config.attn_impl != model.opts.attn_impl):
            # the engine owns the serving execution options: without this
            # override no Pallas attention path is reachable from serving
            # (callers habitually pass Model(cfg) with default opts).
            # ModelOptions.__post_init__ validates the value.
            model = dataclasses.replace(
                model, opts=dataclasses.replace(model.opts,
                                                attn_impl=config.attn_impl)
            )
        if (config.kv_quant is not None
                and config.kv_quant != model.opts.kv_quant):
            # same ownership rule as attn_impl: the engine picks the KV
            # storage dtype.  ModelOptions.__post_init__ validates the value.
            model = dataclasses.replace(
                model, opts=dataclasses.replace(model.opts,
                                                kv_quant=config.kv_quant)
            )
        if model.opts.kv_quant != "none":
            reason = kv_quant_reject_reason(model, config.kv_block_size)
            if reason is not None:
                # refuse loudly — a silently-disabled quantized pool would
                # report fp16-sized capacity while claiming int8 savings
                raise ValueError(reason)
        cfg = model.cfg
        # every GEMM site this model executes must resolve 1:1 to a
        # simulator op — the accounting below attributes energy by site
        _check_site_registry(cfg)
        self.model = model
        self.params = params
        self.config = config
        self.chip = chip or AstraChipConfig()
        self.clock = resolve_clock(clock)
        self.token_sink = token_sink
        self._fused = make_fused_decode(model)
        self._queue: deque[Request] = deque()
        self._slots: List[Optional[_Slot]] = [None] * config.max_slots
        self._outbox: List[RequestOutput] = []  # finished, not yet collected
        self._next_id = 0
        self._key = jax.random.PRNGKey(config.seed)
        # ---------------------------------------------- fault containment
        self._step_no = 0  # engine rounds run (fault/ladder attribution)
        self._n_quarantined = 0  # slots terminated by quarantine_slot
        self._n_cancelled = 0    # requests ended by cancel/deadline
        self._n_shed = 0         # queue heads shed by the degraded ladder
        # prefix reuse / chunked paged prefill need every stateful layer's
        # state to be reconstructible from pooled blocks -> pure global attn
        self._suffix_path = all(k == "attn" for k in cfg.layer_kinds)
        # ----------------------------------------------------- KV layout
        self._paged = (config.kv_block_size > 0
                       and any(k in ("attn", "local") for k in cfg.layer_kinds))
        self._pool: Optional[KVBlockPool] = None
        self._prefix: Optional[RadixPrefixTree] = None
        if self._paged:
            bs = config.kv_block_size
            w = -(-config.max_len // bs)
            # pool-capacity arithmetic, checked HERE so admission can never
            # deadlock mid-decode: even with every other slot full, a new
            # request must always find its blocks after evicting the tree
            floor = 1 + config.max_slots * w
            n_blocks = config.kv_pool_blocks or (floor + 2 * w)
            if n_blocks < floor:
                raise ValueError(
                    f"kv_pool_blocks={n_blocks} cannot back max_slots="
                    f"{config.max_slots} x ceil(max_len {config.max_len} / "
                    f"kv_block_size {bs}) = {w} blocks each (+1 scratch): "
                    f"need >= {floor}"
                )
            self._block_size, self._table_width = bs, w
            self._pool = KVBlockPool(n_blocks, bs)
            self._slot_blocks: List[List[int]] = [[] for _ in range(config.max_slots)]
            self._tables_np = np.zeros((config.max_slots, w), np.int32)
            self._tables_dev = jnp.asarray(self._tables_np)
            self._tables_dirty = False
            self._ring_len = (min(config.max_len, cfg.window)
                              if any(k == "local" for k in cfg.layer_kinds) else 0)
            # record *why* reuse is off instead of silently dropping it —
            # kv_stats and the CLI surface this next to the pool counters
            self._prefix_off_reason: Optional[str] = None
            if not config.prefix_cache:
                self._prefix_off_reason = "disabled by config (prefix_cache=False)"
            elif not self._suffix_path:
                self._prefix_off_reason = (
                    "stateful stack: recurrent/windowed layers cannot resume "
                    "from pooled blocks"
                )
            elif not _kv_deterministic(model):
                self._prefix_off_reason = (
                    "non-deterministic KV: a quantized GEMM site runs with "
                    "dynamic scales (run Model.calibrate for static scales)"
                )
            else:
                self._prefix = RadixPrefixTree(bs)
            self._states = model.init_decode_state(
                config.max_slots, config.max_len, paged=(n_blocks, bs)
            )
            # byte accounting: one block's footprint summed across every
            # layer's K+V pools, at the pool's actual storage dtype
            self._pool.bytes_per_block = _pool_bytes_per_block(self._states)
        else:
            self._states = model.init_decode_state(config.max_slots, config.max_len)
        # degraded-mode ladder: pool pressure is a paged-only phenomenon
        # (dense layouts have no pool to squeeze), and only meaningful
        # when the operator hasn't opted back into fail-loud wedging
        self._ladder: Optional[DegradedLadder] = (
            DegradedLadder() if (self._paged and config.degraded_mode) else None)
        self._prefix_admission = True  # ladder level 2 turns this off
        self._admit_progress = False   # >=1 request left the queue this round
        # --------------------------------------------- prefill scheduling
        self._sched: Optional[TokenBudgetScheduler] = None
        self._prefilling: List[int] = []  # PREFILLING slot ids, admission order
        self._admit_stalled = False  # paged admission rolled back this round
        if config.prefill_chunk_tokens < 0:
            raise ValueError(
                f"prefill_chunk_tokens={config.prefill_chunk_tokens} is "
                "negative; pass a per-round token budget or 0 for blocking "
                "admission"
            )
        if config.prefill_chunk_tokens > 0:
            if self._paged and not self._suffix_path:
                # stateful stacks cannot resume recurrent/ring state from
                # pooled blocks mid-prompt; their paged mode admits one-shot
                # (the dense layout of the same arch chunks fine)
                self._sched = None
            else:
                self._sched = TokenBudgetScheduler(
                    SchedulerConfig(config.prefill_chunk_tokens))
        tok_shape = ((config.max_slots, cfg.n_codebooks, 1) if cfg.n_codebooks
                     else (config.max_slots, 1))
        self._cur_tok = jnp.zeros(tok_shape, jnp.int32)
        # the full-seq prefill emits window-sized rings; when the window
        # exceeds the pre-allocated max_len the slotted cache is smaller
        # (init_cache clamps), so prefill must go through the scan path
        self._force_scan_prefill = (
            any(k == "local" for k in cfg.layer_kinds) and config.max_len < cfg.window
        )

    # ------------------------------------------------------------- intake
    def check_request(self, prompt, max_new_tokens: int) -> np.ndarray:
        """Canonicalize and validate a request; returns the int32 prompt.

        Shared with the admission front-end (serve/frontend.py) so invalid
        requests raise at intake — before a queue position or engine id is
        ever taken."""
        prompt = np.asarray(prompt, np.int32)
        if prompt.shape[-1] == 0:
            raise ValueError("empty prompt: a request needs at least one "
                             "prompt token (its logits seed sampling)")
        if max_new_tokens < 0:
            raise ValueError(f"max_new_tokens={max_new_tokens} is negative")
        if prompt.shape[-1] + max_new_tokens > self.config.max_len:
            raise ValueError(
                f"prompt_len {prompt.shape[-1]} + max_new {max_new_tokens} "
                f"exceeds max_len {self.config.max_len}"
            )
        return prompt

    def allocate_request_id(self) -> int:
        """Reserve the next request id without enqueueing anything — the
        front-end ids requests at *its* admission time so a later reject
        and a served request share one id space."""
        rid = self._next_id
        self._next_id += 1
        return rid

    def submit(self, prompt, max_new_tokens: int, eos_id: Optional[int] = None,
               request_id: Optional[int] = None,
               t_submit: Optional[float] = None) -> int:
        """Enqueue a request.  ``request_id`` (from ``allocate_request_id``)
        and ``t_submit`` let the front-end keep its own admission time as
        the latency anchor — queue/TTFT then include front-end backpressure
        waits, not just the engine-side queue."""
        prompt = self.check_request(prompt, max_new_tokens)
        rid = self.allocate_request_id() if request_id is None else request_id
        req = Request(rid, prompt, max_new_tokens, eos_id,
                      t_submit=self.clock() if t_submit is None else t_submit)
        if max_new_tokens == 0:
            # nothing to decode: complete without ever taking a slot
            now = self.clock()
            self._complete(req, [], t_admit=now, t_first=now, events=[])
        else:
            self._queue.append(req)
        return rid

    # ------------------------------------------------------------ engine
    def has_work(self) -> bool:
        return bool(self._queue) or any(s is not None for s in self._slots)

    def run(self) -> List[RequestOutput]:
        """Drain queue and slots; returns every output completed since the
        last collection (``run``/``step``), in submission order.

        Outputs are handed over exactly once — a long-lived engine does
        not accumulate history, and interleaved callers each see only the
        work finished since they last collected.
        """
        outs = self._drain()
        while self.has_work():
            outs.extend(self.step())
        return sorted(outs, key=lambda o: o.request_id)

    def step(self, faults: Optional[Sequence[FaultSpec]] = None) -> List[RequestOutput]:
        """Admit + prefill work + one fused chunk.  Drains and returns the
        requests that finished since the last collection.

        ``faults`` (normally passed by :class:`~repro.serve.supervisor.
        EngineSupervisor`) injects decode faults into this round's chunk:
        a ``step_error`` raises :class:`InjectedStepError` *before* any
        state commit, a ``nonfinite_logits`` poisons the victim slot's
        logits inside the fused scan.  Either way the raised
        :class:`~repro.serve.faults.ServeFault` names the implicated
        slots and every other slot's stream stays bit-identical to a
        fault-free replay; without a supervisor the fault propagates to
        the caller (loud by design)."""
        self._step_no += 1
        with StepTraceAnnotation(tracing.STEP, step_num=self._step_no):
            self._admit()
            if self._sched is not None:
                self._prefill_chunk()
            self._decode_chunk(faults)
            self._check_progress()
        return self._drain()

    def _drain(self) -> List[RequestOutput]:
        outs, self._outbox = self._outbox, []
        return outs

    def _check_progress(self):
        """React to a stalled paged-admission round.

        With ``degraded_mode`` (default) the engine walks the
        :class:`~repro.serve.scheduler.DegradedLadder` — flush the prefix
        tree, then stop prefix admission, then shed the queue head as a
        terminal ``pool_pressure`` fault output — and relaxes one level
        per round with admission progress.  With ``degraded_mode=False``
        it keeps the original fail-loud contract: raise when admission
        can never succeed (possible only when pool invariants were broken
        externally — the construction-time floor makes organic admission
        infallible)."""
        if self._admit_stalled and self._ladder is not None:
            self._degrade()
        elif (self._admit_stalled and self._queue
                and not any(s is not None for s in self._slots)):
            raise RuntimeError(
                "serve engine wedged: paged admission failed with every slot "
                "free, so no retirement can ever release blocks "
                f"({len(self._queue)} request(s) queued, "
                f"{self._pool.n_free} pool blocks free)"
            )
        elif self._admit_progress and self._ladder is not None:
            if self._ladder.relax(self._step_no) == DegradedLadder.NORMAL:
                self._prefix_admission = True
        self._admit_stalled = False
        self._admit_progress = False

    def _degrade(self):
        """One stalled round: escalate the ladder and act at its level."""
        level = self._ladder.escalate(self._step_no)
        if level >= DegradedLadder.FLUSH_PREFIX and self._prefix is not None:
            # free every evictable interned block — cache value traded
            # for admission headroom, hits become recomputes, not faults
            self._prefix.evict(self._pool.n_blocks, self._pool)
        if level >= DegradedLadder.NO_PREFIX_ADMISSION:
            self._prefix_admission = False
        if level >= DegradedLadder.SHED_LOAD and self._queue:
            # bounded: one queue head per stalled round becomes a terminal
            # pool_pressure fault output (retryable once pressure clears)
            req = self._queue.popleft()
            now = self.clock()
            self._complete(req, [], t_admit=now, t_first=now, events=[],
                           fault_reason=FAULT_POOL_PRESSURE)
            self._n_shed += 1

    # ------------------------------------------------------------- admit
    def _admit(self):
        free = [i for i, s in enumerate(self._slots) if s is None]
        n = min(len(free), len(self._queue))
        if n == 0:
            return
        before = len(self._queue)
        with TraceAnnotation(tracing.ADMIT):
            if self._sched is not None:
                self._admit_chunked(free[:n])
            else:
                self._admit_blocking(free[:n])
        if len(self._queue) < before:
            self._admit_progress = True

    def _reserve_blocks(self, req: Request) -> Tuple[List[int], int]:
        """Match + incref prefix blocks and allocate the rest for ``req``.

        Returns (blocks, n_matched).  Atomic: if the pool cannot cover the
        allocation (a forced evict shortfall — impossible under the
        construction-time floor unless the pool was tampered with), every
        incref taken here is rolled back before the ``RuntimeError``
        propagates, so the caller can re-queue the request with no leaked
        refcounts.
        """
        bs = self._block_size
        total = -(-(req.prompt_len + req.max_new_tokens) // bs)
        matched: List[int] = []
        if self._prefix is not None and self._prefix_admission:
            # always leave >= 1 suffix token: the last prompt token's
            # logits seed the first sampled token
            matched = self._prefix.match(
                req.prompt, max_blocks=min((req.prompt_len - 1) // bs, total)
            )
            for blk in matched:
                self._pool.incref(blk)
        need = total - len(matched)
        try:
            if need > self._pool.n_free and self._prefix is not None:
                self._prefix.evict(need - self._pool.n_free, self._pool)
            fresh = self._pool.alloc(need)
        except RuntimeError:
            for blk in matched:
                self._pool.decref(blk)
            raise
        return matched + fresh, len(matched)

    def _install_blocks(self, slot_i: int, blocks: List[int],
                        into_table: bool) -> None:
        """Record a slot's blocks; materialize its table row only when the
        slot is (or is about to be) visible to decode — a PREFILLING slot's
        row stays at scratch so ride-along decode writes land nowhere."""
        self._slot_blocks[slot_i] = blocks
        self._tables_np[slot_i] = 0
        if into_table:
            self._tables_np[slot_i, : len(blocks)] = blocks
        self._tables_dirty = True

    # ------------------------------------------------- blocking admission
    def _admit_blocking(self, slot_ids: List[int]):
        reqs = [self._queue.popleft() for _ in range(len(slot_ids))]
        t_admit = self.clock()
        if self._paged:
            slot_ids, reqs, last_logits, cached = self._prefill_paged(slot_ids, reqs)
            if not reqs:
                return
        else:
            last_logits = self._prefill_dense(slot_ids, reqs)
            cached = [0] * len(reqs)
        with TraceAnnotation(tracing.FIRST_TOKEN):
            self._key, sub = jax.random.split(self._key)
            first = sample_next_token(last_logits, self.config.sampler, sub,
                                      self.model.cfg)
            ids = jnp.asarray(slot_ids, jnp.int32)
            self._cur_tok = self._cur_tok.at[ids].set(first)
            with TraceAnnotation(tracing.HOST_SYNC):
                first_np = np.asarray(first)  # [n, 1] or [n, C, 1]
            t_first = self.clock()
            for j, (i, req) in enumerate(zip(slot_ids, reqs)):
                tok0 = first_np[j]  # [1] or [C, 1]
                slot = _Slot(req, SlotState.DECODING, pos=req.prompt_len,
                             remaining=req.max_new_tokens - 1, filled=req.prompt_len,
                             generated=[tok0], cached=cached[j], t_admit=t_admit,
                             t_first=t_first, events=[(t_first, 1)])
                self._emit_tokens(req, tok0)
                if self._hit_eos(req, tok0) or slot.remaining == 0:
                    self._retire(slot)
                    self._release_blocks(i)
                else:
                    self._slots[i] = slot

    def _packed_prefill_small(self, reqs: List[Request]):
        """Cold prefill of ``reqs`` at batch len(reqs) with dense states."""
        tokens, lengths = pack_prompts([r.prompt for r in reqs], self.model.cfg)
        return packed_prefill(
            self.model, self.params, tokens, lengths, self.config.max_len,
            lengths_static=[r.prompt_len for r in reqs],
            force_scan=self._force_scan_prefill,
        )

    def _prefill_dense(self, slots_ids: List[int], reqs: List[Request]):
        last_logits, small_states = self._packed_prefill_small(reqs)
        ids = jnp.asarray(slots_ids, jnp.int32)
        self._states = scatter_states(self._states, small_states, ids)
        return last_logits

    def _prefill_paged(self, slot_ids: List[int], reqs: List[Request]):
        """Allocate block tables (reusing interned prefix blocks), prefill
        the unmatched work, and intern the new prompt blocks.

        Exception-safe: if a request's blocks cannot be covered (forced
        evict shortfall), its increfs are rolled back and it — plus every
        later popped request, preserving FCFS order — is re-queued at the
        front; the requests admitted before it proceed normally.
        """
        bs, w = self._block_size, self._table_width
        starts: List[int] = []
        adm_slots: List[int] = []
        adm_reqs: List[Request] = []
        for k, (i, req) in enumerate(zip(slot_ids, reqs)):
            try:
                blocks, n_matched = self._reserve_blocks(req)
            except RuntimeError:
                for r in reversed(reqs[k:]):
                    self._queue.appendleft(r)
                self._admit_stalled = True
                break
            self._install_blocks(i, blocks, into_table=True)
            starts.append(n_matched * bs)
            adm_slots.append(i)
            adm_reqs.append(req)
        if not adm_reqs:
            return [], [], None, []
        rows_dev = jnp.asarray(self._tables_np[adm_slots])
        if self._suffix_path:
            suffixes = [r.prompt[..., s:] for r, s in zip(adm_reqs, starts)]
            tokens, lengths = pack_prompts(suffixes, self.model.cfg)
            ctx = self._ctx_bucket(max(
                s + int(tokens.shape[-1]) for s in starts
            ))
            last_logits, self._states = prefill_paged_suffix(
                self.model, self.params, tokens, lengths, self._states,
                rows_dev, jnp.asarray(starts, jnp.int32), ctx,
            )
        else:
            last_logits, small_states = self._packed_prefill_small(adm_reqs)
            self._states = _paged_scatter(
                self._states, small_states, jnp.asarray(adm_slots, jnp.int32),
                rows_dev
            )
        if self._prefix is not None:
            for i, req, start in zip(adm_slots, adm_reqs, starts):
                self._intern_prompt(i, req, start)
        return adm_slots, adm_reqs, last_logits, starts

    def _intern_prompt(self, slot_i: int, req: Request, start: int):
        if not self._prefix_admission:  # ladder level 2+: no new interning
            return
        bs = self._block_size
        nb_full = req.prompt_len // bs
        if nb_full > start // bs:
            self._prefix.insert(req.prompt[..., : nb_full * bs],
                                self._slot_blocks[slot_i][:nb_full], self._pool)

    def _ctx_bucket(self, max_pos: int) -> int:
        """Pow2 context-view width (blocks) covering ``max_pos`` positions —
        bounds the jit-compile count of the suffix prefill."""
        need = -(-max_pos // self._block_size)
        return max(pow2_bucket(need, self._table_width), 1)

    # -------------------------------------------------- chunked admission
    def _admit_chunked(self, slot_ids: List[int]):
        """Claim free slots for waiting requests as PREFILLING — no prefill
        work here; the scheduler feeds their prompts in bounded chunks."""
        t_admit = self.clock()
        new_dense: List[int] = []
        for i in slot_ids:
            if not self._queue:
                break
            req = self._queue[0]
            filled = 0
            if self._paged:
                try:
                    blocks, n_matched = self._reserve_blocks(req)
                except RuntimeError:
                    # FCFS: the head can't fit — don't admit later requests
                    # over it; retry once retirements free blocks
                    self._admit_stalled = True
                    break
                # table row stays at scratch until the slot starts DECODING:
                # ride-along decode writes must not touch its real blocks
                self._install_blocks(i, blocks, into_table=False)
                filled = n_matched * self._block_size
            self._queue.popleft()
            self._slots[i] = _Slot(req, SlotState.PREFILLING, filled=filled,
                                   cached=filled, t_admit=t_admit)
            self._prefilling.append(i)
            if not self._paged:
                new_dense.append(i)
        if new_dense:
            # dense chunked prefill builds the slot state *in place*, so the
            # previous occupant's state must be zeroed (recurrent leaves
            # especially; KV positions are rewritten in prompt order anyway)
            zeros = self.model.init_decode_state(len(new_dense), self.config.max_len)
            self._states = scatter_states(self._states, zeros,
                                          jnp.asarray(new_dense, jnp.int32))

    def _prefill_chunk(self):
        """One bounded prefill dispatch: the scheduler's FCFS chunk plan
        for this round, then DECODING transitions for completed prompts."""
        if not self._prefilling:
            return
        with TraceAnnotation(tracing.PREFILL_CHUNK) as span:
            n_active = sum(1 for s in self._slots
                           if s is not None and s.state is SlotState.DECODING)
            needs = [(i, self._slots[i].req.prompt_len - self._slots[i].filled)
                     for i in self._prefilling]
            plan = self._sched.plan_chunks(needs, n_active)
            if not plan:
                return
            # the dispatched grid and its real tokens (docs/SERVING.md §Tracing)
            span.set_metadata(rows=len(plan) if self._paged else self.config.max_slots,
                              tokens=sum(t for _, t in plan),
                              width=self._chunk_width(plan))
            if self._paged:
                last_logits = self._prefill_chunk_paged(plan)  # [n_sel, 1, ...]
                row_of = {i: j for j, (i, _) in enumerate(plan)}
            else:
                last_logits = self._prefill_chunk_dense(plan)  # [B, 1, ...]
                row_of = {i: i for i, _ in plan}
            done: List[int] = []
            for i, take in plan:
                slot = self._slots[i]
                slot.filled += take
                if slot.filled == slot.req.prompt_len:
                    done.append(i)
            if done:
                self._start_decoding(done, last_logits, [row_of[i] for i in done])

    def _chunk_width(self, plan: List[Tuple[int, int]]) -> int:
        """Pow2 token width of a prefill chunk's grid."""
        return pow2_bucket(max(t for _, t in plan), self.config.prefill_chunk_tokens)

    def _chunk_tokens(self, plan: List[Tuple[int, int]], width: int,
                      rows: Optional[List[int]] = None) -> np.ndarray:
        """Pack each planned slot's next prompt slice into a ``[n, width]``
        (or ``[n, C, width]``) grid.  ``rows`` maps plan entries to grid
        rows (defaults to 0..n-1)."""
        cfg = self.model.cfg
        n = len(plan) if rows is None else self.config.max_slots
        shape = (n, cfg.n_codebooks, width) if cfg.n_codebooks else (n, width)
        toks = np.zeros(shape, np.int32)
        for j, (i, take) in enumerate(plan):
            slot = self._slots[i]
            r = j if rows is None else rows[j]
            toks[r, ..., :take] = slot.req.prompt[..., slot.filled:slot.filled + take]
        return toks

    def _prefill_chunk_paged(self, plan: List[Tuple[int, int]]):
        """Chunked suffix prefill against the paged pool: each selected
        slot's resident prefix is its prefix-cache hit plus its own earlier
        chunks (``starts`` need not be block-aligned)."""
        width = self._chunk_width(plan)
        tokens = jnp.asarray(self._chunk_tokens(plan, width))
        starts = [self._slots[i].filled for i, _ in plan]
        lengths = jnp.asarray([t for _, t in plan], jnp.int32)
        rows_dev = jnp.asarray(np.stack([
            self._real_row(i) for i, _ in plan
        ]))
        ctx = self._ctx_bucket(max(s + width for s in starts))
        # the dispatch waits while the program before it holds the device
        # memory its outputs need (docs/SERVING.md §Tracing)
        with TraceAnnotation(tracing.HOST_SYNC):
            last_logits, self._states = prefill_paged_suffix(
                self.model, self.params, tokens, lengths, self._states,
                rows_dev, jnp.asarray(starts, jnp.int32), ctx,
            )
        return last_logits

    def _real_row(self, slot_i: int) -> np.ndarray:
        row = np.zeros(self._table_width, np.int32)
        blocks = self._slot_blocks[slot_i]
        row[: len(blocks)] = blocks
        return row

    def _prefill_chunk_dense(self, plan: List[Tuple[int, int]]):
        """Chunked dense prefill: one windowed masked scan over the full
        engine state — selected slots advance, everything else is gated."""
        width = self._chunk_width(plan)
        b = self.config.max_slots
        tokens = jnp.asarray(
            self._chunk_tokens(plan, width, rows=[i for i, _ in plan]))
        starts = np.zeros(b, np.int32)
        lengths = np.zeros(b, np.int32)
        for i, take in plan:
            starts[i] = self._slots[i].filled
            lengths[i] = take
        with TraceAnnotation(tracing.HOST_SYNC):
            last_logits, self._states = prefill_window(
                self.model, self.params, tokens, jnp.asarray(starts),
                jnp.asarray(lengths), self._states,
            )
        return last_logits

    def _start_decoding(self, slot_ids: List[int], last_logits, rows: List[int]):
        """PREFILLING -> DECODING: sample each completed prompt's first
        token, expose paged table rows, intern prefix blocks."""
        with TraceAnnotation(tracing.FIRST_TOKEN):
            self._key, sub = jax.random.split(self._key)
            logits = last_logits[jnp.asarray(rows, jnp.int32)]
            first = sample_next_token(logits, self.config.sampler, sub, self.model.cfg)
            ids = jnp.asarray(slot_ids, jnp.int32)
            self._cur_tok = self._cur_tok.at[ids].set(first)
            with TraceAnnotation(tracing.HOST_SYNC):
                first_np = np.asarray(first)
            t_first = self.clock()
            for j, i in enumerate(slot_ids):
                slot = self._slots[i]
                req = slot.req
                tok0 = first_np[j]
                slot.state = SlotState.DECODING
                slot.pos = req.prompt_len
                slot.remaining = req.max_new_tokens - 1
                slot.generated = [tok0]
                slot.t_first = t_first
                slot.events = [(t_first, 1)]
                self._emit_tokens(req, tok0)
                self._prefilling.remove(i)
                if self._paged:
                    self._install_blocks(i, self._slot_blocks[i], into_table=True)
                    if self._prefix is not None:
                        self._intern_prompt(i, req, slot.cached)
                if self._hit_eos(req, tok0) or slot.remaining == 0:
                    self._retire(slot)
                    self._release_blocks(i)
                    self._slots[i] = None

    # ------------------------------------------------------ paged helpers
    def _release_blocks(self, slot_i: int):
        if not self._paged or not self._slot_blocks[slot_i]:
            return
        for blk in self._slot_blocks[slot_i]:
            self._pool.decref(blk)
        self._slot_blocks[slot_i] = []
        # retired rows point back at scratch so the slot's ride-along
        # decode writes can't corrupt a future owner of these blocks
        self._tables_np[slot_i] = 0
        self._tables_dirty = True

    def _block_tables(self) -> BlockTables:
        if self._tables_dirty:
            self._tables_dev = jnp.asarray(self._tables_np)
            self._tables_dirty = False
        return BlockTables(self._tables_dev, jnp.int32(self._ring_len))

    def lower_decode_chunk(self, steps: Optional[int] = None):
        """The fused decode chunk as ``step`` dispatches it (no fault
        gates), lowered against the engine's current state; ``steps``
        defaults to ``chunk_steps``.  ``.compile().as_text()`` is the
        program the device runs."""
        pos = jnp.zeros(self.config.max_slots, jnp.int32)
        return self._fused.lower(
            self.params, self._cur_tok, self._states, pos, self._key,
            steps=steps or self.config.chunk_steps, sampler=self.config.sampler,
            tables=self._block_tables() if self._paged else None)

    # -------------------------------------------------- fault containment
    def quarantine_slot(self, slot_i: int, reason: str,
                        scrub: bool = True) -> None:
        """Terminate the request occupying ``slot_i`` as a terminal fault.

        The request's already-generated tokens become its final output
        (``fault_reason=reason`` — the streamed chunks and the output
        tokens stay equal, exactly like a normal retire), its exclusively
        held pool blocks are scrubbed (NaN containment: attention masks
        *scores*, not values, so ``0 * NaN`` would poison a future owner's
        output) and released, and the slot is freed.  No other slot is
        touched — that is the whole point.
        """
        slot = self._slots[slot_i]
        if slot is None:
            raise ValueError(f"quarantine of empty slot {slot_i}")
        if slot.state is SlotState.PREFILLING:
            self._prefilling.remove(slot_i)
        gen = (np.concatenate(slot.generated, axis=-1)
               if slot.generated else [])
        now = self.clock()
        self._complete(slot.req, gen, slot.t_admit or now,
                       slot.t_first or slot.t_admit or now, slot.events,
                       cached=slot.cached, fault_reason=reason)
        if scrub and self._paged:
            # only blocks nobody else holds: shared (interned) blocks are
            # prompt prefill output — deterministic and never written by
            # this slot's decode, so they cannot carry its poison
            self._scrub_blocks([b for b in self._slot_blocks[slot_i]
                                if self._pool.ref(b) == 1])
        self._release_blocks(slot_i)
        self._slots[slot_i] = None
        if reason in CANCEL_CLASS:
            self._n_cancelled += 1
        else:
            self._n_quarantined += 1

    def _scrub_blocks(self, blocks: List[int]) -> None:
        """Zero the given physical blocks in every layer's K/V pools.

        Dense layouts need no analogue: admission fully overwrites a
        slot's state before it is ever read (``scatter_states``), and the
        finite guard only inspects active slots.
        """
        if not blocks:
            return
        from repro.models.attention import PagedKVCache, QuantPagedKVCache

        idx = jnp.asarray(blocks, jnp.int32)

        def scrub(node):
            if isinstance(node, (PagedKVCache, QuantPagedKVCache)):
                def z(arr):
                    # units pools [U, n_blocks, kv, bs, hd]; rem [n_blocks, ...]
                    return (arr.at[:, idx].set(0) if arr.ndim == 5
                            else arr.at[idx].set(0))
                return node._replace(k=z(node.k), v=z(node.v))
            return node

        self._states = jax.tree.map(
            scrub, self._states,
            is_leaf=lambda x: isinstance(x, (PagedKVCache, QuantPagedKVCache)),
        )

    def cancel(self, request_id: int, reason: str = CANCELLED) -> bool:
        """Terminate a queued or in-flight request (client intent).

        Mid-decode cancellation goes through :meth:`quarantine_slot`, so
        the request's KV blocks are released immediately — freeing pool
        capacity is the point of cancelling.  The terminal output (tokens
        generated so far, ``fault_reason=reason``) flows through the
        normal outbox.  Returns False when the id is not queued or
        in-flight (already finished, or never seen).
        """
        for j, req in enumerate(self._queue):
            if req.id == request_id:
                del self._queue[j]
                now = self.clock()
                self._complete(req, [], t_admit=now, t_first=now, events=[],
                               fault_reason=reason)
                self._n_cancelled += 1
                return True
        for i, slot in enumerate(self._slots):
            if slot is not None and slot.req.id == request_id:
                # client-intent termination never poisoned anything — the
                # slot decoded finite tokens until now — so skip the scrub
                self.quarantine_slot(i, reason, scrub=False)
                return True
        return False

    def audit(self, external_refs: Sequence[int] = ()) -> Dict[str, object]:
        """Cross-check every piece of serving bookkeeping; raise on drift.

        Verifies (a) outbox/queue/slot request-id disjointness and
        exactly-once outbox discipline, (b) the PREFILLING list against
        slot states, and — on paged layouts — (c) every block's refcount
        against its actual holders (slot tables + prefix tree +
        ``external_refs``, e.g. a supervisor's pool-pressure holds),
        (d) pool free-list consistency, and (e) device block-table rows
        against host slot state.  Raises ``RuntimeError`` on the first
        violation; returns a report dict (``leaked_blocks``/``leaked_bytes``
        are always 0 when it returns) for tests and stats.
        """
        out_ids = [o.request_id for o in self._outbox]
        if len(set(out_ids)) != len(out_ids):
            raise RuntimeError(
                f"audit: duplicate request ids in outbox ({out_ids})")
        live_ids = {s.req.id for s in self._slots if s is not None}
        live_ids |= {r.id for r in self._queue}
        stale = set(out_ids) & live_ids
        if stale:
            raise RuntimeError(
                f"audit: request id(s) {sorted(stale)} are simultaneously "
                "finished (outbox) and live (queue/slot)")
        for i in self._prefilling:
            s = self._slots[i]
            if s is None or s.state is not SlotState.PREFILLING:
                raise RuntimeError(
                    f"audit: prefilling list names slot {i} but the slot "
                    f"is {'empty' if s is None else s.state}")
        report: Dict[str, object] = {
            "paged": self._paged,
            "slots_live": sum(s is not None for s in self._slots),
            "queued": len(self._queue),
            "outbox": len(out_ids),
            "leaked_blocks": 0,
            "leaked_bytes": 0,
        }
        if not self._paged:
            return report
        self._pool.check_consistent()
        expected: Dict[int, int] = {}
        for blocks in self._slot_blocks:
            for b in blocks:
                expected[b] = expected.get(b, 0) + 1
        tree_blocks = (self._prefix.interned_blocks()
                       if self._prefix is not None else [])
        for b in tree_blocks:
            expected[b] = expected.get(b, 0) + 1
        for b in external_refs:
            expected[b] = expected.get(b, 0) + 1
        drift = [(b, self._pool.ref(b), expected.get(b, 0))
                 for b in range(1, self._pool.n_blocks)
                 if self._pool.ref(b) != expected.get(b, 0)]
        if drift:
            b, have, want = drift[0]
            raise RuntimeError(
                f"audit: {len(drift)} block(s) with refcount drift — e.g. "
                f"block {b}: pool ref {have} vs {want} actual holder(s) "
                "(slot tables + prefix tree + external refs)")
        for i, slot in enumerate(self._slots):
            row = self._tables_np[i]
            blocks = self._slot_blocks[i]
            if slot is None and blocks:
                raise RuntimeError(
                    f"audit: empty slot {i} still holds blocks {blocks}")
            if slot is None or slot.state is SlotState.PREFILLING:
                if row.any():
                    raise RuntimeError(
                        f"audit: slot {i} "
                        f"({'empty' if slot is None else 'PREFILLING'}) has "
                        "a non-scratch device table row — ride-along decode "
                        "writes could corrupt another slot's blocks")
            else:
                want_row = np.zeros_like(row)
                want_row[: len(blocks)] = blocks
                if not np.array_equal(row, want_row):
                    raise RuntimeError(
                        f"audit: slot {i} device table row {row.tolist()} "
                        f"!= host blocks {blocks}")
        report.update(
            pool_blocks=self._pool.n_blocks, live_blocks=self._pool.n_live,
            free_blocks=self._pool.n_free, tree_blocks=len(tree_blocks),
            external_refs=len(list(external_refs)),
        )
        return report

    # ------------------------------------------------------------- chunk
    @staticmethod
    def _resolve_victim(hint: Optional[int], active: List[int]) -> int:
        """Map a FaultSpec slot *hint* onto a slot active this round, so
        seeded schedules stay meaningful whatever the admission pattern."""
        return active[0] if hint is None else active[hint % len(active)]

    def _decode_chunk(self, faults: Optional[Sequence[FaultSpec]] = None):
        active = [i for i, s in enumerate(self._slots)
                  if s is not None and s.state is SlotState.DECODING]
        if not active:
            return
        for spec in (faults or ()):
            if spec.kind == FAULT_STEP_ERROR:
                # whole-dispatch failure, raised BEFORE any state commit:
                # healthy slots simply skip one chunk (under greedy
                # sampling their token streams are chunk-boundary
                # independent, so they stay bit-identical)
                victim = self._resolve_victim(spec.slot, active)
                raise InjectedStepError(
                    f"injected device error at engine step {self._step_no} "
                    f"(slot {victim})", slots=(victim,))
        poison = None
        poisoned = sorted({self._resolve_victim(s.slot, active)
                           for s in (faults or ()) if s.kind == FAULT_NONFINITE})
        if poisoned:
            p = np.zeros(self.config.max_slots, bool)
            p[poisoned] = True
            poison = jnp.asarray(p)
        steps = min(self.config.chunk_steps,
                    min(self._slots[i].remaining for i in active))
        with TraceAnnotation(tracing.DECODE_DISPATCH, steps=steps):
            pos = np.zeros(self.config.max_slots, np.int32)
            for i in active:
                pos[i] = self._slots[i].pos
            mask = None
            if (self._sched is not None and not self._paged
                    and len(active) < sum(s is not None for s in self._slots)):
                # dense + PREFILLING slots present: gate ride-along state
                # updates so half-prefilled recurrent/KV state stays intact
                m = np.zeros(self.config.max_slots, bool)
                m[active] = True
                mask = jnp.asarray(m)
            self._key, sub = jax.random.split(self._key)
            # the dispatch waits while a prefill program holds the device
            # memory its outputs need (docs/SERVING.md §Tracing)
            with TraceAnnotation(tracing.HOST_SYNC):
                toks, finite, (next_tok, states, _, _) = self._fused(
                    self.params, self._cur_tok, self._states, jnp.asarray(pos), sub,
                    steps=steps, sampler=self.config.sampler,
                    tables=self._block_tables() if self._paged else None,
                    active=mask, poison=poison,
                )
        self._states = states
        self._cur_tok = next_tok
        with TraceAnnotation(tracing.HOST_SYNC):
            toks_np = np.asarray(toks)  # [B, steps] or [B, C, steps]
            finite_np = np.asarray(finite)  # [B] bool, ANDed over the chunk
        bad = [i for i in active if not finite_np[i]]
        with TraceAnnotation(tracing.RETIRE):
            t_now = self.clock()
            for i in active:
                if i in bad:
                    # the slot's tokens this chunk are garbage (sampled from
                    # non-finite logits): don't emit or account them — the
                    # request ends at its pre-fault stream via quarantine
                    continue
                slot = self._slots[i]
                slot.generated.append(toks_np[i])
                slot.events.append((t_now, steps))
                self._emit_tokens(slot.req, toks_np[i])
                slot.pos += steps
                slot.remaining -= steps
                if slot.remaining == 0 or self._hit_eos(slot.req, toks_np[i]):
                    self._retire(slot)
                    self._release_blocks(i)
                    self._slots[i] = None
        if bad:
            # healthy slots are fully committed above; the fault names
            # exactly the poisoned slots (injected or organic NaN alike)
            raise NonFiniteLogitsError(
                f"non-finite logits at engine step {self._step_no} for "
                f"slot(s) {bad}", slots=tuple(bad))

    # ------------------------------------------------------------ retire
    def _hit_eos(self, req: Request, toks: np.ndarray) -> bool:
        if req.eos_id is None or toks.ndim > 1:  # no EOS over codebook grids
            return False
        return bool(np.any(toks == req.eos_id))

    def _trim_eos(self, req: Request, toks: np.ndarray) -> np.ndarray:
        """Clip a token chunk at the request's first EOS (inclusive) —
        the same truncation ``_retire`` applies to the concatenated output,
        so streamed chunks match the final tokens exactly."""
        if req.eos_id is None or toks.ndim > 1:
            return toks
        hits = np.nonzero(toks == req.eos_id)[0]
        return toks[: hits[0] + 1] if hits.size else toks

    def _emit_tokens(self, req: Request, toks: np.ndarray) -> None:
        """Incremental drain: push freshly generated host tokens to the
        registered sink (EOS-trimmed).  The sink sees every request's
        tokens exactly once, in order; finished ``RequestOutput``s still
        go through the outbox."""
        if self.token_sink is not None:
            toks = self._trim_eos(req, toks)
            if toks.shape[-1]:
                self.token_sink(req.id, toks)

    def _retire(self, slot: _Slot):
        gen = np.concatenate(slot.generated, axis=-1)
        if slot.req.eos_id is not None and gen.ndim == 1:
            hits = np.nonzero(gen == slot.req.eos_id)[0]
            if hits.size:
                gen = gen[: hits[0] + 1]  # keep the EOS, drop overshoot
        # EOS can truncate mid-chunk: reconcile the final arrival event so
        # the timing token count matches the tokens actually delivered
        overshoot = sum(n for _, n in slot.events) - int(gen.shape[-1])
        if overshoot > 0 and slot.events:
            t_last, n_last = slot.events[-1]
            slot.events[-1] = (t_last, n_last - overshoot)
        self._complete(slot.req, gen, slot.t_admit, slot.t_first, slot.events,
                       cached=slot.cached)

    def _complete(self, req: Request, gen, t_admit: float, t_first: float,
                  events: List[Tuple[float, int]], cached: int = 0,
                  fault_reason: Optional[str] = None):
        gen = np.asarray(gen, np.int32)
        if gen.size == 0:
            shape = (req.prompt.shape[0], 0) if req.prompt.ndim == 2 else (0,)
            gen = np.zeros(shape, np.int32)
        hw = None
        if self.config.astra_accounting:
            with TraceAnnotation(tracing.ACCOUNTING):
                hw = request_hardware_report(
                    self.model.cfg, self.chip, req.prompt_len, int(gen.shape[-1]),
                    cached_prompt_len=cached,
                )
        timing = request_timing(req.t_submit, t_admit, t_first, events, self.clock())
        self._outbox.append(RequestOutput(
            req.id, req.prompt, gen, timing.wall_time_s, hw, timing,
            fault_reason=fault_reason,
        ))

    # ------------------------------------------------------------- stats
    @property
    def prefix_stats(self) -> Dict[str, int]:
        """Radix-tree/pool counters (empty when the prefix cache is off)."""
        if self._prefix is None:
            return {}
        t = self._prefix
        return {
            "hits": t.hits, "misses": t.misses, "hit_tokens": t.hit_tokens,
            "evictions": t.evictions, "interned_blocks": len(t),
            "free_blocks": self._pool.n_free,
        }

    @property
    def kv_stats(self) -> Dict[str, object]:
        """KV-memory layout counters (docs/SERVING.md §KV quantization);
        ``{}`` on the dense layout.  ``bytes_per_block`` is the storage
        footprint of one physical block summed over every layer's K+V
        pools at their actual dtype — int8 pools report ~half the fp16
        figure, which is exactly the capacity claim BENCH_kv_quant
        checks.  ``prefix_cache_off_reason`` explains a disabled prefix
        cache instead of letting reuse vanish silently."""
        if not self._paged:
            return {}
        out: Dict[str, object] = {
            "kv_quant": self.model.opts.kv_quant,
            "block_size": self._block_size,
            "pool_blocks": self._pool.n_blocks,
            "live_blocks": self._pool.n_live,
            "free_blocks": self._pool.n_free,
            "bytes_per_block": self._pool.bytes_per_block,
            "pool_bytes": self._pool.total_bytes,
            "live_bytes": self._pool.live_bytes,
            "prefix_cache": self._prefix is not None,
        }
        if self._prefix is None and self._prefix_off_reason:
            out["prefix_cache_off_reason"] = self._prefix_off_reason
        if self._ladder is not None:
            out["degraded_level"] = self._ladder.level_name
            out["degraded_transitions"] = len(self._ladder.transitions)
            out["prefix_admission"] = self._prefix_admission
        return out

    @property
    def scheduler_stats(self) -> Dict[str, int]:
        """Chunked-prefill counters; ``{"active": False}`` under blocking
        admission (including the paged-stateful fallback)."""
        if self._sched is None:
            return {"active": False}
        return {"active": True, **self._sched.stats}

    def stats(self) -> Dict[str, object]:
        """One-call serving snapshot: fault/degraded counters plus the
        per-subsystem stat dicts (docs/SERVING.md §Fault tolerance)."""
        return {
            "step": self._step_no,
            "queued": len(self._queue),
            "slots_live": sum(s is not None for s in self._slots),
            "n_quarantined": self._n_quarantined,
            "n_cancelled": self._n_cancelled,
            "n_shed": self._n_shed,
            "degraded_level": (self._ladder.level_name
                               if self._ladder is not None else "normal"),
            "degraded_transitions": (list(self._ladder.transitions)
                                     if self._ladder is not None else []),
            "kv": self.kv_stats,
            "prefix": self.prefix_stats,
            "scheduler": self.scheduler_stats,
        }

    # -------------------------------------------------------- convenience
    def generate_batch(self, prompts: Sequence[np.ndarray], max_new_tokens: int,
                       eos_id: Optional[int] = None) -> List[RequestOutput]:
        """Submit a batch and drain — outputs in prompt order.

        Collects (and discards) any outputs still pending from earlier
        interleaved submissions; callers mixing APIs should use
        ``submit`` + ``run``/``step`` directly.
        """
        ids = [self.submit(p, max_new_tokens, eos_id) for p in prompts]
        by_id = {o.request_id: o for o in self.run()}
        return [by_id[rid] for rid in ids]
