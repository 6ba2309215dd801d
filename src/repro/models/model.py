"""Model facade: init / loss / train_step / prefill / decode + input specs.

``input_specs(cfg, shape)`` produces ShapeDtypeStruct stand-ins for every
model input of a given assignment cell — the dry-run lowers against these
(weak-type-correct, shardable, no device allocation).  Modality frontends
are stubs per the assignment: MusicGen gets the EnCodec token grid,
Llama-Vision gets precomputed patch embeddings.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeConfig
from repro.core.plan import ExecutionPlan
from repro.models.transformer import (
    ModelOptions, decode_step, forward, init_decode_state, init_params,
    suffix_forward,
)


def cross_entropy(logits: jax.Array, labels: jax.Array, z_loss: float = 0.0):
    """logits [..., V] fp32, labels [...] int32 with -1 = masked."""
    valid = (labels >= 0).astype(jnp.float32)
    labels = jnp.maximum(labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0] - logz
    loss = -(ll * valid).sum() / jnp.maximum(valid.sum(), 1.0)
    if z_loss:
        loss = loss + z_loss * ((logz**2) * valid).sum() / jnp.maximum(valid.sum(), 1.0)
    return loss


# Leaves every forward casts to the activations' dtype before use: GEMM
# weights, MoE expert weights and embedding tables.  Left as they are: the
# f32 GEMMs (MoE router; RG-LRU gates, which read the f32 conv output),
# norms, biases and recurrence parameters.
_SERVE_CAST = re.compile(
    r"(?<!router)(?<!w_a)(?<!w_x)/w$|mlp/w_(up|gate|down)$|embedding/table$")


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ArchConfig
    opts: ModelOptions = ModelOptions()

    # --------------------------------------------------------------- plan
    @property
    def plan(self) -> ExecutionPlan:
        return self.opts.plan

    def with_plan(self, plan) -> "Model":
        """Same model under a different ExecutionPlan (any ``from_spec``
        form: plan, preset/mode name, JSON rules, dict)."""
        plan = ExecutionPlan.from_spec(plan)
        return dataclasses.replace(
            self, opts=dataclasses.replace(self.opts, plan=plan, cc=None)
        )

    def calibrate(self, params, batch) -> "Model":
        """PTQ calibration pass: one exact-mode forward over ``batch`` with
        per-site activation observers; returns the model with per-site
        static ``act_scale`` baked into its plan."""
        return self.with_plan(self.plan.calibrate(self, params, batch))

    # ------------------------------------------------------------- params
    def init(self, key) -> Dict[str, Any]:
        return init_params(key, self.cfg)

    def serving_params(self, params) -> Dict[str, Any]:
        """``params`` as a server holds them: the leaves each forward casts
        to the compute dtype anyway (``_SERVE_CAST``) stored in it.  Exact
        mode's outputs are unchanged and a bf16 model's resident weights
        halve; quantized modes quantize from the stored values."""
        dt = jnp.bfloat16 if self.cfg.dtype == "bfloat16" else jnp.float32

        def one(path, a):
            name = jax.tree_util.keystr(path, simple=True, separator="/")
            # multi-codebook embeddings are summed in f32 before the cast
            keep = self.cfg.n_codebooks and name.endswith("table")
            return a if keep or not _SERVE_CAST.search(name) else a.astype(dt)

        return jax.tree_util.tree_map_with_path(one, params)

    def param_shapes(self) -> Dict[str, Any]:
        return jax.eval_shape(lambda: self.init(jax.random.PRNGKey(0)))

    # ------------------------------------------------------------- train
    def loss(self, params, batch) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        tokens = batch["tokens"]
        logits, aux, _ = forward(
            params, tokens, self.cfg, self.opts, vision_embeds=batch.get("vision_embeds")
        )
        if self.cfg.n_codebooks:
            # tokens [B, C, S], logits [B, S, C, V]: shift along S per codebook
            labels = tokens[:, :, 1:].transpose(0, 2, 1)  # [B, S-1, C]
            loss = cross_entropy(logits[:, :-1], labels, self.opts.z_loss)
        else:
            loss = cross_entropy(logits[:, :-1], tokens[:, 1:], self.opts.z_loss)
        loss = loss + aux
        return loss, {"loss": loss, "aux": aux}

    # ------------------------------------------------------------- serve
    def prefill(self, params, batch, max_len: Optional[int] = None):
        """Full-sequence pass emitting serving states.  ``max_len`` pads the
        KV caches to the serve engine's pre-allocated slot length."""
        logits, _, states = forward(
            params, batch["tokens"], self.cfg, self.opts,
            vision_embeds=batch.get("vision_embeds"), return_states=True,
            max_len=max_len,
        )
        return logits, states

    def decode(self, params, token, states, pos, block_tables=None):
        return decode_step(params, token, states, pos, self.cfg, self.opts,
                           block_tables=block_tables)

    def prefill_suffix(self, params, tokens, states, table, start, ctx_blocks: int):
        """Prefix-aware packed prefill against the paged KV pool (pure
        global-attention stacks; docs/SERVING.md).  Returns full suffix
        logits plus the updated pooled states."""
        return suffix_forward(params, tokens, self.cfg, self.opts, states,
                              table, start, ctx_blocks)

    def init_decode_state(self, batch: int, max_len: int, paged=None):
        """``paged=(n_blocks, block_size)`` builds the pooled layout for
        attn/local caches (see ``transformer.init_decode_state``).  With
        ``opts.kv_quant="int8"`` the pools are int8 blocks carrying the
        plan's calibrated per-KV-head scales."""
        return init_decode_state(self.cfg, batch, max_len, paged,
                                 kv_quant=self.opts.kv_quant,
                                 plan=self.opts.plan)


# ---------------------------------------------------------------- specs
def _tok_spec(cfg: ArchConfig, batch: int, seq: int):
    if cfg.n_codebooks:
        return jax.ShapeDtypeStruct((batch, cfg.n_codebooks, seq), jnp.int32)
    return jax.ShapeDtypeStruct((batch, seq), jnp.int32)


def input_specs(cfg: ArchConfig, shape: ShapeConfig, dtype=jnp.bfloat16) -> Dict[str, Any]:
    """Model-input stand-ins for one assignment cell.

    train/prefill: {"tokens", ["vision_embeds"]}.
    decode: {"token" (one step), "states" (KV/recurrent state of seq_len),
             "pos"} — lowered against ``serve_step``.
    """
    if shape.kind in ("train", "prefill"):
        specs: Dict[str, Any] = {"tokens": _tok_spec(cfg, shape.global_batch, shape.seq_len)}
        if cfg.vision_tokens:
            specs["vision_embeds"] = jax.ShapeDtypeStruct(
                (shape.global_batch, cfg.vision_tokens, cfg.d_model), dtype
            )
        return specs
    # decode
    states = jax.eval_shape(lambda: init_decode_state(cfg, shape.global_batch, shape.seq_len))
    specs = {
        "token": _tok_spec(cfg, shape.global_batch, 1),
        "states": states,
        "pos": jax.ShapeDtypeStruct((), jnp.int32),
    }
    return specs
