"""End-to-end training driver: data -> model -> AdamW -> checkpoint/restart.

The runnable face of the same stack the dry-run lowers for 512 chips:
identical step function, sharding rules, and checkpoint format — only the
mesh differs (a host mesh over every local device here,
``make_production_mesh`` on the pod).

Fault tolerance is on by default: atomic async checkpoints every
``--ckpt-every`` steps, automatic resume from the newest checkpoint, and an
optional injected fault schedule (``--fail-at 12,27``) to demonstrate
recovery.  Determinism: the data pipeline is step-addressable, so a resumed
run reproduces the fault-free loss trajectory bit-for-bit.

Usage (tiny model, a few hundred steps on CPU):
  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b --reduced \
      --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.checkpoint import CheckpointManager
from repro.configs import get_arch
from repro.data import DataConfig, Prefetcher, SyntheticLMDataset
from repro.launch.compile_cache import place_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models.model import Model
from repro.models.transformer import ModelOptions
from repro.optim import AdamWConfig, adamw_init, adamw_update, cosine_schedule
from repro.parallel.sharding import activation_mesh, batch_specs, param_specs
from repro.runtime import FaultInjector, run_with_restarts


def build_train_step(model: Model, ocfg: AdamWConfig, total_steps: int, warmup: int):
    def train_step(params, opt_state, batch):
        def loss_fn(p):
            return model.loss(p, batch)[0]

        loss, grads = jax.value_and_grad(loss_fn)(params)
        lr_scale = cosine_schedule(opt_state["step"], warmup, total_steps)
        params2, opt2, stats = adamw_update(params, grads, opt_state, ocfg, lr_scale)
        return params2, opt2, {"loss": loss, **stats}

    return train_step


def main(argv=None, mesh=None):
    """Train per ``argv``.  ``mesh`` (optional) replaces the mesh the flags
    would build — e.g. a one-device mesh to compare a sharded run against."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--reduced", action="store_true", help="tiny same-family config (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--fail-at", default="", help="comma-separated steps to inject faults")
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--prefetch", action="store_true",
                    help="background data prefetch w/ straggler deadline+backup")
    ap.add_argument("--prefetch-timeout", type=float, default=30.0)
    args = ap.parse_args(argv)
    place_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if mesh is None:
        mesh = make_production_mesh() if args.production_mesh else make_host_mesh()
    model = Model(cfg, ModelOptions())
    ocfg = AdamWConfig(lr=args.lr)

    dcfg = DataConfig(
        vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch, seed=args.seed,
        n_codebooks=cfg.n_codebooks, vision_tokens=cfg.vision_tokens, d_model=cfg.d_model,
    )
    dataset = SyntheticLMDataset(dcfg)
    prefetcher = Prefetcher(dataset, timeout_s=args.prefetch_timeout).start() if args.prefetch else None
    injector = FaultInjector(int(s) for s in args.fail_at.split(",") if s)

    param_shapes = model.param_shapes()
    p_shard = param_specs(param_shapes, mesh)
    opt_shapes = jax.eval_shape(adamw_init, param_shapes)
    o_shard = {
        "m": param_specs(opt_shapes["m"], mesh),
        "v": param_specs(opt_shapes["v"], mesh),
        "step": jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
    }
    step_fn_inner = build_train_step(model, ocfg, args.steps, args.warmup)
    jit_step = jax.jit(
        step_fn_inner,
        in_shardings=((p_shard, o_shard, None)),
        out_shardings=(p_shard, o_shard, None),
        donate_argnums=(0, 1),
    )

    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None

    def init_state():
        with mesh, activation_mesh(mesh):
            params = jax.jit(model.init, out_shardings=p_shard)(jax.random.PRNGKey(args.seed))
            opt = adamw_init(params)
        return {"params": params, "opt": opt}

    t_last = [time.time()]

    def step_fn(state, step):
        injector.check(step)
        batch = prefetcher.get(step) if prefetcher else dataset.batch_at(step)
        b_shard = batch_specs(batch, mesh)
        batch = jax.tree.map(lambda a, s: jax.device_put(a, s), batch, b_shard)
        with mesh, activation_mesh(mesh):
            params, opt, metrics = jit_step(state["params"], state["opt"], batch)
        return {"params": params, "opt": opt}, metrics

    def on_metrics(step, m):
        if step % args.log_every == 0 or step == args.steps - 1:
            dt = time.time() - t_last[0]
            t_last[0] = time.time()
            print(f"step {step:5d}  loss {m['loss']:.4f}  gnorm {m.get('grad_norm', 0):.3f}  "
                  f"({dt:.2f}s)", flush=True)

    summary = run_with_restarts(
        init_state=init_state, step_fn=step_fn, n_steps=args.steps,
        ckpt_manager=mgr, ckpt_every=args.ckpt_every, on_metrics=on_metrics,
    )
    if mgr:
        mgr.save(args.steps - 1, summary["state"], metadata={"final": True})
        mgr.wait()
    if prefetcher:
        prefetcher.stop()
        if prefetcher.substituted_steps:
            print(f"straggler substitutions at steps {prefetcher.substituted_steps}")
    losses = [m["loss"] for m in summary["metrics"].values()]
    print(f"done: {len(losses)} steps, restarts={summary['restarts']}, "
          f"first-loss {losses[0]:.4f} last-loss {losses[-1]:.4f}, wall {summary['wall_s']:.1f}s")
    return summary


if __name__ == "__main__":
    main()
