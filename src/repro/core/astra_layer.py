"""ASTRA as a first-class execution mode for model matmuls.

``astra_matmul(x, w, cc)`` is the single entry point the model zoo uses
for every GEMM, so the whole framework can switch between:

* ``exact``  — bf16/f32 reference (training, dry-runs, baselines),
* ``int8``   — ASTRA *expectation*: symmetric int8 PTQ + integer matmul +
  dequant.  Bit-identical to the mean of the stochastic process (zero
  stream-rounding error); this is the deployable TPU fast path and what the
  dry-run lowers for serving.  Backed by ``repro.kernels.int8_matmul``.
* ``sc``     — bit-exact 128-bit stochastic stream simulation of the OSSM
  array (``repro.kernels.stoch_matmul``), used for accuracy validation.
  ~STREAM_LEN x the bytes of int8 — a validation mode, like the paper's own
  simulator.

Modes are threaded through the models per GEMM *site*: ``cc`` may be a
plain :class:`ComputeConfig` (uniform behavior, the legacy API) or a
:class:`BoundSite` — a named GEMM site bound to an
:class:`~repro.core.plan.ExecutionPlan` that resolves it to a per-site
``ComputeConfig`` (and feeds the calibration observer during
``plan.calibrate``).  Site naming matches the architecture simulator's op
graph (``L3.attn.qk``, ``L0.rglru.in_proj``, ``lm_head``, ...) so executed
GEMMs and modeled ops share one registry — see ``repro.core.plan``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.quant import quantize

MODES = ("exact", "int8", "sc")


@dataclasses.dataclass(frozen=True)
class ComputeConfig:
    mode: str = "exact"
    x_gen: str = "thermometer"
    w_gen: str = "bresenham"
    use_pallas: bool = False  # Pallas kernels (interpreted off-TPU) vs jnp refs
    act_scale: Optional[float] = None  # static activation scale (PTQ-calibrated)

    def __post_init__(self):
        if self.mode not in MODES:
            raise ValueError(
                f"unknown compute mode {self.mode!r}; valid modes: {', '.join(MODES)}"
            )


EXACT = ComputeConfig("exact")
INT8 = ComputeConfig("int8")
SC = ComputeConfig("sc")


@dataclasses.dataclass(frozen=True)
class BoundSite:
    """A named GEMM site (or a group of sites sharing one scanned trace)
    bound to an ExecutionPlan.  ``astra_matmul`` accepts this wherever it
    accepts a plain ComputeConfig; resolution happens at trace time.

    ``sites`` holds every *concrete* site id this call stands for — the
    scan-over-layers executes one trace for all pattern units, so a single
    call site covers ``L0.attn.qk, L2.attn.qk, ...`` at once.  The plan
    must resolve them identically (enforced by ``resolve_group``).
    """

    plan: object  # repro.core.plan.ExecutionPlan (duck-typed: no core->plan import)
    sites: Tuple[str, ...]

    def resolved(self) -> ComputeConfig:
        return self.plan.resolve_group(self.sites)

    @property
    def observing(self) -> bool:
        return getattr(self.plan, "_observer", None) is not None


def resolve_cc(cc: Union[ComputeConfig, BoundSite]) -> ComputeConfig:
    """Plain ComputeConfig for either form of ``cc`` (no observation)."""
    return cc.resolved() if isinstance(cc, BoundSite) else cc


def runs_exact(cc: Union[ComputeConfig, BoundSite]) -> bool:
    """Whether this GEMM takes the plain exact fast path — i.e. neither
    quantized nor tapped by a calibration observer."""
    return resolve_cc(cc).mode == "exact" and not (
        isinstance(cc, BoundSite) and cc.observing
    )


def _maybe_observe(cc: Union[ComputeConfig, BoundSite], x: jax.Array) -> None:
    """Feed the activation absmax to the plan's calibration observer (if
    any) — the single tap point shared by all astra matmul entry points."""
    if isinstance(cc, BoundSite):
        obs = getattr(cc.plan, "_observer", None)
        if obs is not None:
            amax = jnp.max(jnp.abs(x.astype(jnp.float32)))
            jax.debug.callback(functools.partial(obs.record, cc.sites), amax)


def astra_matmul(
    x: jax.Array,
    w: jax.Array,
    cc: Union[ComputeConfig, BoundSite] = EXACT,
    *,
    site: Optional[str] = None,
    plan=None,
) -> jax.Array:
    """[..., K] @ [K, N] under the selected ASTRA execution mode.

    ``cc`` is either a uniform :class:`ComputeConfig` or a
    :class:`BoundSite`; alternatively pass ``site=`` and ``plan=`` to bind
    here (``astra_matmul(x, w, site="L0.attn.q_proj", plan=plan)``).
    """
    if plan is not None:
        names = (site,) if isinstance(site, str) else tuple(site or ("<anon>",))
        cc = BoundSite(plan, names)
    if isinstance(cc, BoundSite):
        _maybe_observe(cc, x)
        cc = cc.resolved()
    if cc.mode == "exact":
        return jnp.matmul(x, w.astype(x.dtype))
    lead = x.shape[:-1]
    k = x.shape[-1]
    x2 = x.reshape(-1, k)
    xq = quantize(x2, axis=None, scale=cc.act_scale)
    wq = quantize(w, axis=0)  # per-output-channel
    if cc.mode == "int8":
        if cc.use_pallas:
            from repro.kernels.int8_matmul import ops as int8_ops

            out = int8_ops.int8_matmul(xq, wq)
        else:
            from repro.core.quant import int8_matmul_exact

            out = int8_matmul_exact(xq, wq)
    else:  # sc
        if cc.use_pallas:
            from repro.kernels.stoch_matmul import ops as sc_ops

            out = sc_ops.stoch_matmul(xq, wq, x_gen=cc.x_gen, w_gen=cc.w_gen)
        else:
            from repro.core.ossm import sc_matmul_value

            out = sc_matmul_value(xq, wq, cc.x_gen, cc.w_gen)
    return out.reshape(*lead, w.shape[-1]).astype(x.dtype)


def astra_batched_matmul(x: jax.Array, w: jax.Array,
                         cc: Union[ComputeConfig, BoundSite]) -> jax.Array:
    """Batched GEMM with a *per-batch* second operand: ``[..., M, K] @
    [..., K, N]`` with shared leading dims — the dynamic-tensor form the
    attention qk/pv products and per-expert MoE GEMMs take.

    Exact mode stays a plain einsum; quantized modes vmap ``astra_matmul``
    over the flattened batch, which gives each batch element (e.g. each
    attention head) its own dynamic quantization scales — matching how the
    OSSM array streams both operands per tile.  Pallas kernels are 2-D; the
    batched path always uses the jnp references.
    """
    if runs_exact(cc):
        return jnp.matmul(x, w.astype(x.dtype))
    cc_run = dataclasses.replace(resolve_cc(cc), use_pallas=False)
    _maybe_observe(cc, x)
    lead = x.shape[:-2]
    xf = x.reshape((-1,) + x.shape[-2:])
    wf = jnp.broadcast_to(w, lead + w.shape[-2:]).reshape((-1,) + w.shape[-2:])
    out = jax.vmap(lambda a, b: astra_matmul(a, b, cc_run))(xf, wf)
    return out.reshape(lead + out.shape[-2:])
