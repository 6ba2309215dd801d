import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 " + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run: AOT lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: 512 host
placeholder devices stand in for 2 TPU v5e pods.  For each cell we

  1. build the step function (train_step for ``train`` shapes; prefill /
     decode serve steps otherwise),
  2. resolve in/out shardings from ``repro.parallel.sharding`` rules,
  3. ``jax.jit(...).lower(**input_specs).compile()``,
  4. record ``memory_analysis()`` (fits-per-device evidence),
     ``cost_analysis()`` (FLOPs / bytes for the roofline), and the
     collective schedule parsed from the optimized HLO,
  5. dump one JSON artifact per cell under --out.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-32b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""
import argparse
import json
import re
import time
import traceback
from typing import Any, Dict, Tuple

import jax

from repro.configs import ARCHS, SHAPES, get_arch, shape_applicable
from repro.launch.mesh import make_production_mesh
from repro.models.model import Model, input_specs
from repro.models.transformer import ModelOptions
from repro.optim import AdamWConfig, adamw_init, adamw_update
from repro.parallel.sharding import activation_mesh, batch_specs, param_specs, state_specs

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all", "collective-permute")
_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}
_SHAPE_RE = re.compile(r"\b([a-z]+[0-9]+|pred)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


_GROUP_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")
_GROUP_LIST_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")


def _group_size(line: str) -> int:
    m = _GROUP_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUP_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 1


def _link_traffic(op: str, result_bytes: int, g: int) -> float:
    """Per-device link bytes for ring algorithms of group size g.

    result_bytes is the per-device *result* shape from SPMD HLO:
    all-reduce result == full reduced tensor (2(g-1)/g rings);
    all-gather result == gathered tensor ((g-1)/g leaves each device);
    reduce-scatter result == scattered shard (operand = g x result).
    """
    if g <= 1:
        return 0.0
    if op == "all-reduce":
        return 2.0 * result_bytes * (g - 1) / g
    if op == "all-gather":
        return result_bytes * (g - 1) / g
    if op == "reduce-scatter":
        return float(result_bytes) * (g - 1)
    if op == "all-to-all":
        return result_bytes * (g - 1) / g
    return float(result_bytes)  # collective-permute


def parse_collectives(hlo_text: str, scan_trip_count: int = 1) -> Dict[str, Any]:
    """Collective schedule from optimized SPMD HLO.

    Result-shape bytes per instruction; instructions whose metadata places
    them inside a scan body (op_name contains "/while/") execute
    ``scan_trip_count`` times and are weighted accordingly.
    """
    stats: Dict[str, Dict[str, float]] = {}
    for line in hlo_text.splitlines():
        m = re.search(r"=\s*(\(?[a-z0-9\[\],{} ]*\)?)\s*\b(" + "|".join(COLLECTIVES) + r")(-start)?\(", line)
        if not m:
            continue
        op = m.group(2)
        result_part = m.group(1)
        shapes = _SHAPE_RE.findall(result_part)
        b = sum(_shape_bytes(dt, dims) for dt, dims in shapes)
        g = _group_size(line)
        mult = scan_trip_count if "/while/" in line else 1
        s = stats.setdefault(op, {"count": 0, "bytes": 0, "traffic_bytes": 0.0})
        s["count"] += mult
        s["bytes"] += b * mult
        s["traffic_bytes"] += _link_traffic(op, b, g) * mult
    return stats


def _unwrap_cost(ca):
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    return dict(ca) if ca else {}


# --------------------------------------------------------------- HLO costs
# XLA's cost_analysis() counts while-loop bodies ONCE (trip counts are not
# folded in), which silently drops ~all FLOPs of a scan-over-layers model.
# We therefore re-count dots from the optimized HLO text, weighting each
# instruction by the trip counts of the loops it sits in (depth d =>
# prod(trips[:d]); scan metadata marks nesting as repeated "/while/" path
# segments).  Fusion subcomputations are skipped for byte accounting (their
# intermediates never hit HBM); dots are counted wherever they appear.
_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\([^)]*\)\s*->.*\{")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*([a-z0-9]+)\[([0-9,]*)\]")
_DOT_RE = re.compile(r"\bdot\(")
_DOT_ARGS_RE = re.compile(r"\bdot\(([^)]*)\)")
_OPND_RE = re.compile(r"%([\w.\-]+)")
_CDIMS_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")


def _dims(s: str):
    return [int(d) for d in s.split(",")] if s else []


def _loop_mult(line: str, trips) -> int:
    depth = line.count("/while/")
    mult = 1
    for d in range(min(depth, len(trips))):
        mult *= max(trips[d], 1)
    return mult


def parse_hlo_costs(hlo_text: str, trips=(1,)) -> Dict[str, float]:
    """Trip-weighted FLOPs and HBM-byte proxy from optimized SPMD HLO.

    flops: 2 * prod(out_dims) * prod(lhs_contracting_dims) per dot,
    weighted by the trip counts of enclosing scans (depth d from repeated
    "/while/" metadata segments => prod(trips[:d])).
    bytes: dot operand+output bytes (traffic a perfectly-fused TPU program
    still moves through HBM/VMEM) + non-fusion instruction outputs (fusion
    subcomputation intermediates never materialize).
    """
    trips = tuple(int(t) for t in trips) or (1,)
    shapes: Dict[str, Tuple[str, str]] = {}
    flops = 0.0
    dot_bytes = 0.0
    out_bytes = 0.0
    in_fusion = False
    for line in hlo_text.splitlines():
        h = _HDR_RE.match(line.strip())
        if h:
            in_fusion = "fused" in h.group(1) or "wrapped" in h.group(1)
            continue
        m = _DEF_RE.match(line)
        if not m:
            continue
        name, dtype, dims = m.group(1), m.group(2), m.group(3)
        shapes[name] = (dtype, dims)
        nbytes = _shape_bytes(dtype, dims)
        mult = _loop_mult(line, trips)
        if _DOT_RE.search(line):
            cm = _CDIMS_RE.search(line)
            args = _DOT_ARGS_RE.search(line)
            ops = _OPND_RE.findall(args.group(1)) if args else []
            if cm is not None and ops and ops[0] in shapes:
                lhs_dims = _dims(shapes[ops[0]][1])
                contract = 1
                for i in _dims(cm.group(1)):
                    if i < len(lhs_dims):
                        contract *= lhs_dims[i]
                out_elems = 1
                for d in _dims(dims):
                    out_elems *= d
                flops += 2.0 * out_elems * contract * mult
                operand_bytes = sum(
                    _shape_bytes(*shapes[o]) for o in ops[:2] if o in shapes
                )
                dot_bytes += (operand_bytes + nbytes) * mult
        elif not in_fusion:
            out_bytes += nbytes * mult
    return {
        "hlo_flops": flops,
        "dot_bytes": dot_bytes,
        "other_bytes": out_bytes,
        "hlo_bytes": dot_bytes + out_bytes,
    }


def build_step(arch_name: str, shape_name: str, mesh, opts: ModelOptions,
               strategy: str = "tp_fsdp", kv_layout: str = "heads"):
    """Returns (jitted_fn, arg_specs) ready to .lower(*arg_specs)."""
    cfg = get_arch(arch_name)
    shape = SHAPES[shape_name]
    model = Model(cfg, opts)
    specs = input_specs(cfg, shape)
    param_shapes = model.param_shapes()
    p_shard = param_specs(param_shapes, mesh, strategy)

    if shape.kind == "train":
        ocfg = AdamWConfig()
        opt_shapes = jax.eval_shape(adamw_init, param_shapes)
        o_shard = {
            "m": param_specs(opt_shapes["m"], mesh, strategy),
            "v": param_specs(opt_shapes["v"], mesh, strategy),
            "step": jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
        }
        b_shard = batch_specs(specs, mesh, strategy)

        def train_step(params, opt_state, batch):
            def loss_fn(p):
                return model.loss(p, batch)[0]

            loss, grads = jax.value_and_grad(loss_fn)(params)
            params2, opt2, stats = adamw_update(params, grads, opt_state, ocfg)
            return params2, opt2, {"loss": loss, **stats}

        fn = jax.jit(
            train_step,
            in_shardings=(p_shard, o_shard, b_shard),
            out_shardings=(p_shard, o_shard, None),
            donate_argnums=(0, 1),
        )
        return fn, (param_shapes, opt_shapes, specs)

    if shape.kind == "prefill":
        b_shard = batch_specs(specs, mesh, strategy)

        def prefill_step(params, batch):
            return model.prefill(params, batch)

        state_shapes = jax.eval_shape(
            lambda p, b: model.prefill(p, b)[1], param_shapes, specs
        )
        s_shard = state_specs(state_shapes, mesh, shape.global_batch, kv_layout=kv_layout)
        fn = jax.jit(
            prefill_step,
            in_shardings=(p_shard, b_shard),
            out_shardings=(None, s_shard),
        )
        return fn, (param_shapes, specs)

    # decode: one token against a seq_len state
    s_shard = state_specs(specs["states"], mesh, shape.global_batch, kv_layout=kv_layout)
    tok_shard = batch_specs({"token": specs["token"]}, mesh)["token"]
    scalar = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())

    def serve_step(params, token, states, pos):
        return model.decode(params, token, states, pos)

    fn = jax.jit(
        serve_step,
        in_shardings=(p_shard, tok_shard, s_shard, scalar),
        out_shardings=(None, s_shard),
        donate_argnums=(2,),
    )
    return fn, (param_shapes, specs["token"], specs["states"], specs["pos"])


def resolve_auto(shape, cfg=None, model_axis: int = 16, n_devices: int = 256) -> Tuple[str, str]:
    """Per-shape optimized defaults, from the EXPERIMENTS.md SPerf hillclimbs:
    train -> pure ZeRO-3 (kills row-parallel activation all-reduces; experts
    keep EP; >=3.2x on every train cell); decode -> TP-only weights (no
    optimizer state to shard) + flash-decoding seq-sharded KV (up to 23x and
    the difference between fitting HBM or not).  Prefill and long-context
    keep the tp_fsdp baseline: measured, TP-only weights slightly regress
    small-model prefill (weight gathers there are cheap, activations
    dominate), and the recurrent-state long_500k cells have no KV cache for
    kv=seq to help."""
    if shape.kind == "train":
        # pure ZeRO-3 needs the batch to cover every device; otherwise the
        # leftover axis would just replicate work — keep TP there
        if shape.global_batch % n_devices == 0:
            return "fsdp", "heads"
        return "tp_fsdp", "heads"
    if shape.name.startswith("decode"):
        # flash-decoding seq-sharded KV only pays off when head-sharding
        # can't cover the axis (GQA kv-heads not divisible -> replication);
        # otherwise heads-sharding avoids the softmax partial all-reduces
        if cfg is not None and cfg.n_kv_heads % model_axis == 0:
            return "tp", "heads"
        return "tp", "seq"
    return "tp_fsdp", "heads"


def run_cell(arch_name: str, shape_name: str, multi_pod: bool, opts: ModelOptions,
             strategy: str = "tp_fsdp", kv_layout: str = "heads") -> Dict[str, Any]:
    cfg = get_arch(arch_name)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    rec: Dict[str, Any] = {
        "arch": arch_name, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "params": cfg.param_count(), "active_params": cfg.active_param_count(),
        "strategy": strategy, "kv_layout": kv_layout,
    }
    if strategy == "auto":
        strategy, kv_layout = resolve_auto(shape, cfg, n_devices=512 if multi_pod else 256)
        rec["strategy"], rec["kv_layout"] = strategy, kv_layout
    if not ok:
        rec.update(status="skipped", reason=why)
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    try:
        t0 = time.time()
        fn, arg_specs = build_step(arch_name, shape_name, mesh, opts, strategy, kv_layout)
        with mesh, activation_mesh(mesh, strategy):
            lowered = fn.lower(*arg_specs)
            t1 = time.time()
            compiled = lowered.compile()
            t2 = time.time()
        ma = compiled.memory_analysis()
        ca = _unwrap_cost(compiled.cost_analysis())
        hlo = compiled.as_text()
        coll = parse_collectives(hlo, scan_trip_count=max(cfg.n_pattern_units, 1))
        # trip-count nest: unit scan, then any per-time scan (sLSTM)
        hlo_costs = parse_hlo_costs(hlo, trips=(max(cfg.n_pattern_units, 1), shape.seq_len))
        rec.update(hlo_costs)
        rec.update(
            status="ok",
            lower_s=round(t1 - t0, 2),
            compile_s=round(t2 - t1, 2),
            flops=ca.get("flops", 0.0),
            bytes_accessed=ca.get("bytes accessed", 0.0),
            memory={
                k: getattr(ma, k)
                for k in (
                    "argument_size_in_bytes", "output_size_in_bytes",
                    "temp_size_in_bytes", "generated_code_size_in_bytes",
                    "alias_size_in_bytes", "peak_memory_in_bytes",
                )
                if hasattr(ma, k)
            },
            collectives=coll,
            n_devices=int(jax.device_count()),
        )
    except Exception as e:  # a failure here is a bug in our sharding config
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--attn-impl", default="naive", choices=["naive", "flash"])
    ap.add_argument("--remat", default="true", choices=["true", "false"])
    ap.add_argument("--strategy", default="tp_fsdp", choices=["tp_fsdp", "fsdp", "ep_dp", "tp", "auto"])
    ap.add_argument("--kv", default="heads", choices=["heads", "seq"])
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    opts = ModelOptions(attn_impl=args.attn_impl, remat=args.remat == "true")
    os.makedirs(args.out, exist_ok=True)
    cells = []
    archs = list(ARCHS) if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if (args.all or args.both_meshes) else [args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                cells.append((a, s, mp))

    failures = 0
    for a, s, mp in cells:
        tag = f"{a}__{s}__{'2x16x16' if mp else '16x16'}"
        if args.tag:
            tag += f"__{args.tag}"
        path = os.path.join(args.out, tag + ".json")
        if os.path.exists(path):
            rec = json.load(open(path))
            print(f"[cached] {tag}: {rec['status']}")
            continue
        print(f"[dryrun] {tag} ...", flush=True)
        rec = run_cell(a, s, mp, opts, args.strategy, args.kv)
        with open(path, "w") as f:
            json.dump(rec, f, indent=2)
        print(f"  -> {rec['status']}"
              + (f" compile={rec.get('compile_s')}s flops={rec.get('flops'):.3e}" if rec["status"] == "ok" else
                 f" ({rec.get('reason', rec.get('error', ''))[:200]})"),
              flush=True)
        failures += rec["status"] == "error"
    print(f"done; {failures} failures")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
