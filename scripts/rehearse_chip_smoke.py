#!/usr/bin/env python3
"""Rehearse ``chip_smoke.py`` without a chip.

    python scripts/rehearse_chip_smoke.py serve    # phases a-c + probes, reduced, CPU
    python scripts/rehearse_chip_smoke.py probe    # the probes at the smoke's prompts
    python scripts/rehearse_chip_smoke.py train    # --four-chips path, reduced, 4 CPU devices
    python scripts/rehearse_chip_smoke.py compile  # full-width programs for a described v5e

    python scripts/rehearse_chip_smoke.py probe --plant drop-newest-key
    python scripts/rehearse_chip_smoke.py train --plant half-batch

``serve``, ``probe`` and ``train`` run chip_smoke's own functions on the
CPU (Pallas kernels interpreted), skipping its TPU check — the only
place that check is bypassed.  ``serve`` runs everything at
``.reduced()`` width with short prompts.  ``probe`` runs the logit probe
at the smoke's prompt lengths on stablelm's family cut to d_model 64 but
kept at 24 layers with one KV head per query head, and the kernel probe
at stablelm's published head shapes.  ``train`` runs the four-chip
comparison at reduced width on 4 CPU devices.  ``--plant`` plants one
fault and passes only if some limit catches it: ``drop-newest-key`` makes
the paged decode kernel ignore the newest key, ``half-batch`` takes the
2x2 run's steps on half the batch (a lost data shard).

``compile`` lowers the serving programs (paged prefill and fused decode,
per phase) and the train step (one chip and a 2x2 mesh) at published
widths and compiles them for a described ``v5e:2x2`` topology from
``jax.eval_shape`` shapes, printing each program's
``memory_analysis()``.  A compile that passes is not a chip run.  Each
mode runs in its own process (device flags are read at start-up).
"""
from __future__ import annotations

import os
import sys

MODE = sys.argv[1] if len(sys.argv) > 1 else ""
PLANTS = {"probe": "drop-newest-key", "train": "half-batch"}
PLANT = sys.argv[3] if sys.argv[2:3] == ["--plant"] and len(sys.argv) == 4 else None
if MODE not in ("serve", "probe", "train", "compile") or (
        len(sys.argv) > 2 and PLANT != PLANTS.get(MODE)):
    sys.exit(__doc__)
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_LOG_DIR", "disabled")
if MODE == "train":
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import functools  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import chip_smoke  # noqa: E402


def _gib(n):
    return f"{n / 2**30:.2f} GiB"


def _report(name, compiled, seconds):
    ma = compiled.memory_analysis()
    kernel = "tpu_custom_call" in compiled.as_text()
    print(f"{name}: compiled in {seconds:.1f} s; args {_gib(ma.argument_size_in_bytes)}, "
          f"outputs {_gib(ma.output_size_in_bytes)}, temps {_gib(ma.temp_size_in_bytes)}, "
          f"aliased {_gib(ma.alias_size_in_bytes)}; tpu_custom_call {kernel}", flush=True)


def _on(sharding, tree):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
                        tree)


def compile_serve(one_chip):
    """The engine's programs for the chip_smoke batch at published widths."""
    import repro.kernels.flash_attention.kernel as flash_kernel
    import repro.kernels.paged_attention.kernel as paged_kernel
    from repro.configs import get_arch
    from repro.models.attention import BlockTables
    from repro.models.model import Model
    from repro.models.transformer import ModelOptions
    from repro.serve import GREEDY, make_fused_decode
    from repro.serve.prefill import prefill_paged_suffix
    from repro.serve.scheduler import pow2_bucket

    # this process is on the CPU backend, which would pick interpret mode;
    # the chip compiles the kernels, so compile them here too
    for mod in (paged_kernel, flash_kernel):
        mod.interpret_mode = lambda interpret=None: bool(interpret)

    cfg = get_arch(chip_smoke.SERVE_ARCH)
    n, bs = chip_smoke.N_REQUESTS, chip_smoke.KV_BLOCK
    s_max = max(chip_smoke.PROMPT_LENS)
    max_len = s_max + chip_smoke.GEN + 1
    w = -(-max_len // bs)
    n_blocks = 1 + n * w + 2 * w  # the engine's auto pool size
    ctx = pow2_bucket(-(-s_max // bs), w)
    params = _on(one_chip, jax.eval_shape(lambda: Model(cfg).serving_params(Model(cfg).init(
        jax.random.PRNGKey(0)))))
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32, sharding=one_chip)
    for label, mode, attn_impl in chip_smoke.PHASES:
        model = Model(cfg, ModelOptions(plan=mode, attn_impl=attn_impl))
        states = _on(one_chip, jax.eval_shape(
            lambda m=model: m.init_decode_state(n, max_len, paged=(n_blocks, bs))))
        t0 = time.perf_counter()
        prefill = jax.jit(functools.partial(prefill_paged_suffix, model),
                          static_argnames=("ctx_blocks",))
        c = prefill.lower(params, i32((n, s_max)), i32((n,)), states, i32((n, w)),
                          i32((n,)), ctx_blocks=ctx).compile()
        _report(f"[{label}] paged prefill [{n}, {s_max}] ctx {ctx} blocks", c,
                time.perf_counter() - t0)
        key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
        tables = BlockTables(i32((n, w)), i32(()))
        for steps in (chip_smoke.CHUNK_STEPS, chip_smoke.CHUNK_STEPS - 1):
            t0 = time.perf_counter()
            c = make_fused_decode(model).lower(
                params, i32((n, 1)), states, i32((n,)), key, steps=steps,
                sampler=GREEDY, tables=tables).compile()
            _report(f"[{label}] fused decode steps={steps}", c, time.perf_counter() - t0)


def compile_train(topo):
    """The tp_fsdp train step at published widths: one chip and 2x2."""
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec

    from repro.configs import get_arch
    from repro.launch.train import build_train_step
    from repro.models.model import Model
    from repro.optim import AdamWConfig, adamw_init
    from repro.parallel.sharding import activation_mesh, batch_specs, param_specs

    cfg = get_arch(chip_smoke.TRAIN_ARCH)
    model = Model(cfg)
    p_shapes = model.param_shapes()
    o_shapes = jax.eval_shape(adamw_init, p_shapes)
    batch = {"tokens": jax.ShapeDtypeStruct((chip_smoke.TRAIN_BATCH, chip_smoke.TRAIN_SEQ),
                                            jnp.int32)}
    for shape in ((1, 1), (2, 2)):
        devs = np.array(topo.devices[: shape[0] * shape[1]]).reshape(shape)
        mesh = Mesh(devs, ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        p_sh = param_specs(p_shapes, mesh)
        o_sh = {"m": param_specs(o_shapes["m"], mesh), "v": param_specs(o_shapes["v"], mesh),
                "step": NamedSharding(mesh, PartitionSpec())}
        b_sh = batch_specs(batch, mesh)
        # a fresh step function per mesh: the activation-sharding hints
        # read the mesh while tracing, so a reused function would replay
        # the first mesh's trace
        step = build_train_step(model, AdamWConfig(), chip_smoke.TRAIN_STEPS, 1)
        fn = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh), out_shardings=(p_sh, o_sh, None),
                     donate_argnums=(0, 1))
        t0 = time.perf_counter()
        with activation_mesh(mesh):
            c = fn.lower(p_shapes, o_shapes, batch).compile()
        _report(f"[train {shape[0]}x{shape[1]}] {cfg.name} batch {chip_smoke.TRAIN_BATCH} "
                f"seq {chip_smoke.TRAIN_SEQ} (per device)", c, time.perf_counter() - t0)


def plant_drop_newest_key():
    """The paged decode kernel ignores each slot's newest key."""
    import repro.kernels.paged_attention as pa

    real = pa.paged_attention_decode

    def dropped(q, k_pool, v_pool, table, kv_len, *args, **kw):
        return real(q, k_pool, v_pool, table, kv_len - 1, *args, **kw)

    pa.paged_attention_decode = dropped


def plant_half_batch():
    """The 2x2 run's steps see only the first half of each batch."""
    from repro.launch import train

    real_main, real_build = train.main, train.build_train_step

    def build_half(*args):
        step = real_build(*args)
        return lambda params, opt, batch: step(
            params, opt, jax.tree.map(lambda a: a[: a.shape[0] // 2], batch))

    def main(argv, mesh):
        train.build_train_step = build_half if mesh.devices.size > 1 else real_build
        try:
            return real_main(argv, mesh=mesh)
        finally:
            train.build_train_step = real_build

    train.main = main


def judge(readings):
    """Exit non-zero unless the limits catch exactly when a fault is planted."""
    over = [what for what, (err, tol) in readings.items() if err > tol]
    print(f"over the limit: {over or 'none'}" + (f" (planted: {PLANT})" if PLANT else ""))
    if bool(over) != bool(PLANT):
        sys.exit("rehearsal failed: " + ("the planted fault was not caught" if PLANT
                                         else "readings over the limit"))


def main():
    if MODE == "serve":
        # shorter prompts keep the interpreted kernels quick; the engine
        # path, phases and checks are chip_smoke's own
        chip_smoke.PROMPT_LENS = (16, 32, 48)
        chip_smoke.serve_smoke(reduced=True, require_compiled=False)
    elif MODE == "probe":
        if PLANT:
            plant_drop_newest_key()
        cfg = chip_smoke.serve_config(reduced=True, n_layers=24, n_kv_heads=4)
        print(f"[probe] logits: {cfg.name}, {cfg.n_layers} layers, d_model {cfg.d_model}, "
              f"{cfg.n_heads} heads of {cfg.head_dim}; prompts {chip_smoke.prompt_lengths()}")
        params, prompts = chip_smoke.serve_params(cfg, 0)
        readings, _ = chip_smoke.logit_readings(chip_smoke.phase_models(cfg), params, prompts)
        readings.update(chip_smoke.kernel_readings(chip_smoke.serve_config())[0])
        judge(readings)
    elif MODE == "train":
        if PLANT:
            plant_half_batch()
        chip_smoke.TRAIN_SEQ = 64
        judge(chip_smoke.train_readings(chip_smoke.train_runs(reduced=True)))
    else:
        from jax.experimental import topologies
        from jax.experimental.compilation_cache import compilation_cache
        from jax.sharding import SingleDeviceSharding

        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        print(f"described topology: {topo.devices[0].device_kind} x {len(topo.devices)}")
        compile_serve(SingleDeviceSharding(topo.devices[0]))
        compile_train(topo)
    print("rehearsal ok:", MODE, PLANT or "")


if __name__ == "__main__":
    main()
