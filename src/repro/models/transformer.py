"""The composable decoder stack: pattern units, scan-over-layers, serving state.

A model is ``block_pattern`` repeated ``n_units`` times (stacked params,
executed under ``lax.scan`` so the HLO stays one-unit-sized regardless of
depth) plus an unrolled remainder (e.g. RecurrentGemma's 26 = 8x3 + 2).
Every block kind exposes a sequence path (training / prefill, optionally
emitting its serving state) and a decode path (one token + state).

Serving state is a pytree mirroring the parameter stacking:
``{"units": {"slot<i>": stacked_state}, "rem": [state...]}``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.astra_layer import ComputeConfig, EXACT
from repro.core.plan import ExecutionPlan
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import xlstm as xlstm_mod
from repro.models.layers import (
    embed_tokens, embedding_init, head_apply, head_init,
    mlp_apply, mlp_init, norm_apply, norm_init,
)


@dataclasses.dataclass(frozen=True)
class ModelOptions:
    """Execution options.  GEMM modes are governed by ``plan`` (an
    :class:`~repro.core.plan.ExecutionPlan`, or any ``from_spec`` form:
    preset name, mode string, JSON rules, dict).

    ``cc`` is the DEPRECATED one-release shim for the old global-mode API:
    ``ModelOptions(cc=ComputeConfig("int8"))`` lowers to
    ``ExecutionPlan.uniform(cc)`` (same numerics as before — weight GEMMs
    quantized, dynamic qk/pv exact) and is then normalized to ``None`` so
    equal plans hash/compare equal regardless of which spelling built them.
    """

    plan: Optional[Union[ExecutionPlan, str, dict, ComputeConfig]] = None
    cc: Optional[ComputeConfig] = None  # DEPRECATED -> uniform plan
    # naive = jnp einsum everywhere; flash = Pallas attention kernels
    # (interpret mode chosen by backend): flash_attention on the sequence path, the
    # gather-free paged_attention kernels on decode and paged suffix
    # prefill.  Kernels cover exact qk/pv only — quantized dynamic sites
    # fall back to the astra-batched path per site.
    attn_impl: str = "naive"
    # KV *storage* quantization for the paged pool: "none" keeps blocks in
    # model dtype; "int8" stores them as symmetric int8 against calibrated
    # static per-KV-head scales (plan.kv_scales, baked by Model.calibrate).
    # Paged layouts only — the serve engine refuses dense + kv_quant.
    kv_quant: str = "none"
    use_rglru_kernel: bool = False
    remat: bool = True
    capacity_factor: float = 1.25
    z_loss: float = 1e-4

    ATTN_IMPLS = ("naive", "flash")
    KV_QUANTS = ("none", "int8")

    def __post_init__(self):
        if self.attn_impl not in self.ATTN_IMPLS:
            raise ValueError(
                f"attn_impl={self.attn_impl!r} unknown; valid: "
                f"{', '.join(self.ATTN_IMPLS)}"
            )
        if self.kv_quant not in self.KV_QUANTS:
            raise ValueError(
                f"kv_quant={self.kv_quant!r} unknown; valid: "
                f"{', '.join(self.KV_QUANTS)}"
            )
        plan = self.plan
        if plan is None:
            plan = ExecutionPlan.uniform(self.cc if self.cc is not None else EXACT)
        elif not isinstance(plan, ExecutionPlan):
            plan = ExecutionPlan.from_spec(plan)
        object.__setattr__(self, "plan", plan)
        object.__setattr__(self, "cc", None)  # normalized: plan is the truth


# ------------------------------------------------------------------ blocks
def _has_mlp(cfg: ArchConfig, kind: str) -> bool:
    return kind in ("attn", "local", "xattn", "rglru") and (cfg.d_ff > 0 or cfg.moe is not None)


def block_init(key, cfg: ArchConfig, kind: str):
    k1, k2, k3 = jax.random.split(key, 3)
    p: Dict[str, Any] = {"pre_norm": norm_init(cfg.d_model, cfg.norm)}
    if kind in ("attn", "local", "xattn"):
        p["core"] = attn.attn_init(k1, cfg, cross=(kind == "xattn"))
    elif kind == "rglru":
        p["core"] = rglru_mod.rglru_init(k1, cfg)
    elif kind == "mlstm":
        p["core"] = xlstm_mod.mlstm_init(k1, cfg)
    elif kind == "slstm":
        p["core"] = xlstm_mod.slstm_init(k1, cfg)
    if _has_mlp(cfg, kind):
        p["post_norm"] = norm_init(cfg.d_model, cfg.norm)
        p["mlp"] = moe_mod.moe_init(k2, cfg) if cfg.moe is not None else mlp_init(k2, cfg)
    return p


def block_apply_seq(
    p, x, cfg: ArchConfig, kind: str, opts: ModelOptions, layers: Tuple[int, ...],
    vision_embeds=None, return_state: bool = False, max_len: Optional[int] = None,
):
    """Returns (x, state, aux).  ``layers`` holds the concrete layer
    indices this trace stands for (one index for unrolled remainder
    layers; every unit's index for a scanned pattern slot) — they form the
    ``L{li}.{kind}.*`` site group the plan resolves."""
    sites = opts.plan.binding(kind, layers)
    h = norm_apply(p["pre_norm"], x, cfg.norm, cfg.norm_eps)
    state = None
    if kind in ("attn", "local", "xattn"):
        out, cache = attn.attn_seq(
            p["core"], h, cfg, kind=kind, sites=sites,
            use_flash=(opts.attn_impl == "flash"),
            kv_src=vision_embeds, return_cache=return_state, max_len=max_len,
        )
        state = cache
    elif kind == "rglru":
        out, state = rglru_mod.rglru_seq(p["core"], h, cfg, sites, opts.use_rglru_kernel, return_state)
    elif kind == "mlstm":
        out, state = xlstm_mod.mlstm_seq(p["core"], h, cfg, sites, return_state)
    elif kind == "slstm":
        out, state = xlstm_mod.slstm_seq(p["core"], h, cfg, sites, return_state)
    x = x + out
    aux = jnp.zeros((), jnp.float32)
    if _has_mlp(cfg, kind):
        h2 = norm_apply(p["post_norm"], x, cfg.norm, cfg.norm_eps)
        if cfg.moe is not None:
            mo, aux = moe_mod.moe_apply(p["mlp"], h2, cfg, sites, opts.capacity_factor)
        else:
            mo = mlp_apply(p["mlp"], h2, cfg, sites)
        x = x + mo
    if return_state and state is None:
        state = jnp.zeros((x.shape[0],), jnp.float32)  # placeholder leaf
    return x, state, aux


def block_apply_decode(p, x, state, pos, cfg: ArchConfig, kind: str,
                       opts: ModelOptions, layers: Tuple[int, ...],
                       block_tables=None):
    sites = opts.plan.binding(kind, layers)
    h = norm_apply(p["pre_norm"], x, cfg.norm, cfg.norm_eps)
    if kind in ("attn", "local", "xattn"):
        out, state = attn.attn_decode(p["core"], h, state, pos, cfg, kind=kind,
                                      sites=sites, tables=block_tables,
                                      use_kernel=(opts.attn_impl == "flash"))
    elif kind == "rglru":
        out, state = rglru_mod.rglru_decode(p["core"], h, state, cfg, sites)
    elif kind == "mlstm":
        out, state = xlstm_mod.mlstm_decode(p["core"], h, state, cfg, sites)
    elif kind == "slstm":
        out, state = xlstm_mod.slstm_decode(p["core"], h, state, cfg, sites)
    x = x + out
    if _has_mlp(cfg, kind):
        h2 = norm_apply(p["post_norm"], x, cfg.norm, cfg.norm_eps)
        if cfg.moe is not None:
            mo, _ = moe_mod.moe_apply(p["mlp"], h2, cfg, sites, full_capacity=True)
        else:
            mo = mlp_apply(p["mlp"], h2, cfg, sites)
        x = x + mo
    return x, state


def block_state_init(cfg: ArchConfig, kind: str, batch: int, max_len: int,
                     paged: Optional[Tuple[int, int]] = None,
                     kv_quant: str = "none",
                     plan: Optional[ExecutionPlan] = None,
                     layers: Tuple[int, ...] = ()):
    if kind in ("attn", "local") and paged is not None:
        n_blocks, block_size = paged
        if kv_quant == "int8":
            if plan is None:
                raise ValueError("kv_quant='int8' needs a calibrated plan")
            k_scale = plan.kv_group_scale(tuple(f"L{li}.kv.k" for li in layers))
            v_scale = plan.kv_group_scale(tuple(f"L{li}.kv.v" for li in layers))
            return attn.init_paged_quant_cache(  # repro-lint: disable=determinism-gates -- allocation dispatch only; ServeEngine.__init__ runs kv_quant_reject_reason before any engine reaches this path
                cfg, n_blocks, block_size, k_scale, v_scale)
        return attn.init_paged_cache(cfg, n_blocks, block_size)
    if kind in ("attn", "local", "xattn"):
        return attn.init_cache(cfg, kind, batch, max_len)
    if kind == "rglru":
        return rglru_mod.RGLRUState(
            jnp.zeros((batch, cfg.d_rnn), jnp.float32),
            jnp.zeros((batch, cfg.conv_width - 1, cfg.d_rnn), jnp.float32),
        )
    if kind == "mlstm":
        return xlstm_mod.mlstm_state_init(cfg, batch)
    if kind == "slstm":
        return xlstm_mod.slstm_state_init(cfg, batch)
    raise ValueError(kind)


# ------------------------------------------------------------------ stack
def init_params(key, cfg: ArchConfig):
    keys = jax.random.split(key, 4)
    pattern = cfg.block_pattern
    n_units = cfg.n_pattern_units
    params: Dict[str, Any] = {
        "embedding": embedding_init(keys[0], cfg),
        "head": head_init(keys[1], cfg),
        "final_norm": norm_init(cfg.d_model, cfg.norm),
    }
    if n_units:
        unit_keys = jax.random.split(keys[2], n_units)
        units = {}
        for si, kind in enumerate(pattern):
            slot_keys = jax.vmap(lambda k, i=si: jax.random.fold_in(k, i))(unit_keys)
            units[f"slot{si}"] = jax.vmap(lambda k, kk=kind: block_init(k, cfg, kk))(slot_keys)
        params["units"] = units
    rem_kinds = cfg.layer_kinds[n_units * len(pattern):]
    if rem_kinds:
        rkeys = jax.random.split(keys[3], len(rem_kinds))
        params["rem"] = [block_init(rkeys[i], cfg, k) for i, k in enumerate(rem_kinds)]
    return params


def _slot_layers(cfg: ArchConfig, si: int) -> Tuple[int, ...]:
    """Concrete layer indices pattern slot ``si`` stands for across the
    scanned units (the slot's GEMM sites form one plan-resolution group)."""
    P = len(cfg.block_pattern)
    return tuple(u * P + si for u in range(cfg.n_pattern_units))


def _unit_seq(cfg, opts, vision_embeds, return_state, max_len=None):
    pattern = cfg.block_pattern

    def fn(x, unit_params):
        states = {}
        aux = jnp.zeros((), jnp.float32)
        for si, kind in enumerate(pattern):
            x, st, a = block_apply_seq(
                unit_params[f"slot{si}"], x, cfg, kind, opts, _slot_layers(cfg, si),
                vision_embeds=vision_embeds, return_state=return_state, max_len=max_len,
            )
            aux += a
            if return_state:
                states[f"slot{si}"] = st
        return x, (states, aux) if return_state else aux

    return fn


def forward(
    params, tokens, cfg: ArchConfig, opts: ModelOptions,
    vision_embeds=None, return_states: bool = False, max_len: Optional[int] = None,
):
    """Full-sequence pass.  Returns (logits, aux, states|None)."""
    from repro.parallel.sharding import shard_act

    x = shard_act(embed_tokens(params["embedding"], tokens, cfg), ("batch", None, None))
    aux_total = jnp.zeros((), jnp.float32)
    states: Dict[str, Any] = {}
    if "units" in params:
        fn = _unit_seq(cfg, opts, vision_embeds, return_states, max_len)
        if opts.remat:
            fn = jax.checkpoint(fn)
        x, ys = jax.lax.scan(fn, x, params["units"])
        if return_states:
            states["units"], aux_seq = ys
            aux_total += aux_seq.sum()
        else:
            aux_total += ys.sum()
    if "rem" in params:
        rem_base = cfg.n_pattern_units * len(cfg.block_pattern)
        rem_kinds = cfg.layer_kinds[rem_base:]
        rem_states = []
        for i, (p_i, kind) in enumerate(zip(params["rem"], rem_kinds)):
            x, st, a = block_apply_seq(
                p_i, x, cfg, kind, opts, (rem_base + i,), vision_embeds=vision_embeds,
                return_state=return_states, max_len=max_len,
            )
            aux_total += a
            rem_states.append(st)
        if return_states:
            states["rem"] = rem_states
    x = norm_apply(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    logits = head_apply(params["head"], params["embedding"], x, cfg,
                        opts.plan.site("lm_head"))
    return logits, aux_total, (states if return_states else None)


def decode_step(params, token, states, pos, cfg: ArchConfig, opts: ModelOptions,
                block_tables=None):
    """One serving step.  token [B,1] (or [B,C,1] multi-codebook) -> logits.

    ``block_tables`` (an :class:`attn.BlockTables`, optional) routes the
    attn/local cache reads and writes through the paged pool."""
    x = embed_tokens(params["embedding"], token, cfg)
    if "units" in params:
        pattern = cfg.block_pattern

        def fn(x, xs):
            unit_params, unit_states = xs
            new_states = {}
            for si, kind in enumerate(pattern):
                x, st = block_apply_decode(
                    unit_params[f"slot{si}"], x, unit_states[f"slot{si}"], pos,
                    cfg, kind, opts, _slot_layers(cfg, si), block_tables
                )
                new_states[f"slot{si}"] = st
            return x, new_states

        x, new_unit_states = jax.lax.scan(fn, x, (params["units"], states["units"]))
        states = dict(states)
        states["units"] = new_unit_states
    if "rem" in params:
        rem_base = cfg.n_pattern_units * len(cfg.block_pattern)
        rem_kinds = cfg.layer_kinds[rem_base:]
        new_rem = []
        for i, (p_i, st, kind) in enumerate(zip(params["rem"], states["rem"], rem_kinds)):
            x, st2 = block_apply_decode(p_i, x, st, pos, cfg, kind, opts,
                                        (rem_base + i,), block_tables)
            new_rem.append(st2)
        states = dict(states)
        states["rem"] = new_rem
    x = norm_apply(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    logits = head_apply(params["head"], params["embedding"], x, cfg,
                        opts.plan.site("lm_head"))
    return logits, states


def _block_apply_suffix(p, x, state, table, start, cfg: ArchConfig,
                        opts: ModelOptions, layers: Tuple[int, ...],
                        ctx_blocks: int):
    """One pure-attention block over packed suffixes with pooled past KV."""
    sites = opts.plan.binding("attn", layers)
    h = norm_apply(p["pre_norm"], x, cfg.norm, cfg.norm_eps)
    out, state = attn.attn_prefill_paged(
        p["core"], h, state, table, start, cfg, sites=sites, ctx_blocks=ctx_blocks,
        use_kernel=(opts.attn_impl == "flash"),
    )
    x = x + out
    if _has_mlp(cfg, "attn"):
        h2 = norm_apply(p["post_norm"], x, cfg.norm, cfg.norm_eps)
        if cfg.moe is not None:
            mo, _ = moe_mod.moe_apply(p["mlp"], h2, cfg, sites, opts.capacity_factor)
        else:
            mo = mlp_apply(p["mlp"], h2, cfg, sites)
        x = x + mo
    return x, state


def suffix_forward(params, tokens, cfg: ArchConfig, opts: ModelOptions,
                   states, table, start, ctx_blocks: int):
    """Prefix-aware packed prefill for pure global-attention stacks.

    Runs the unmatched suffixes (``tokens [B, S_suf]``, right-padded) in
    one parallel pass against prefix KV already resident in the paged
    pool, writing the suffix KV into each slot's blocks.  This is the
    serve engine's prefix-cache admission path; a cold request is just
    ``start == 0``.  Returns (logits ``[B, S_suf, V]``, new states).
    """
    if any(k != "attn" for k in cfg.layer_kinds):
        raise ValueError(
            f"suffix_forward needs a pure global-attention stack, got "
            f"{set(cfg.layer_kinds)}; recurrent/windowed/cross states cannot "
            "be reconstructed from paged prefix blocks"
        )
    from repro.parallel.sharding import shard_act

    x = shard_act(embed_tokens(params["embedding"], tokens, cfg), ("batch", None, None))
    if "units" in params:
        pattern = cfg.block_pattern

        def fn(x, xs):
            unit_params, unit_states = xs
            new_states = {}
            for si, _kind in enumerate(pattern):
                x, st = _block_apply_suffix(
                    unit_params[f"slot{si}"], x, unit_states[f"slot{si}"],
                    table, start, cfg, opts, _slot_layers(cfg, si), ctx_blocks
                )
                new_states[f"slot{si}"] = st
            return x, new_states

        x, new_unit_states = jax.lax.scan(fn, x, (params["units"], states["units"]))
        states = dict(states)
        states["units"] = new_unit_states
    if "rem" in params:
        rem_base = cfg.n_pattern_units * len(cfg.block_pattern)
        new_rem = []
        for i, (p_i, st) in enumerate(zip(params["rem"], states["rem"])):
            x, st2 = _block_apply_suffix(p_i, x, st, table, start, cfg, opts,
                                         (rem_base + i,), ctx_blocks)
            new_rem.append(st2)
        states = dict(states)
        states["rem"] = new_rem
    x = norm_apply(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    logits = head_apply(params["head"], params["embedding"], x, cfg,
                        opts.plan.site("lm_head"))
    return logits, states


def init_decode_state(cfg: ArchConfig, batch: int, max_len: int,
                      paged: Optional[Tuple[int, int]] = None,
                      kv_quant: str = "none",
                      plan: Optional[ExecutionPlan] = None):
    """Zeroed serving state (the dry-run's decode input spec).

    ``paged = (n_blocks, block_size)`` swaps the attn/local caches for
    shared block pools (``PagedKVCache``, no batch axis — the block table
    carries slot identity); recurrent and xattn states stay dense-slotted.
    ``kv_quant="int8"`` makes the paged pools int8 with per-KV-head scales
    taken from ``plan.kv_scales`` (layers sharing a scanned trace share one
    calibration tap, so the group scale is exact for them).
    """
    pattern = cfg.block_pattern
    n_units = cfg.n_pattern_units
    states: Dict[str, Any] = {}
    if n_units:
        units = {}
        for si, kind in enumerate(pattern):
            one = block_state_init(cfg, kind, batch, max_len, paged,
                                   kv_quant, plan, _slot_layers(cfg, si))
            units[f"slot{si}"] = jax.tree.map(lambda a: jnp.broadcast_to(a[None], (n_units, *a.shape)), one)
        states["units"] = units
    rem_base = n_units * len(pattern)
    rem_kinds = cfg.layer_kinds[rem_base:]
    if rem_kinds:
        states["rem"] = [
            block_state_init(cfg, k, batch, max_len, paged,
                             kv_quant, plan, (rem_base + i,))
            for i, k in enumerate(rem_kinds)
        ]
    return states
