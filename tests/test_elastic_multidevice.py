"""Elastic scaling integration test (subprocess: 8 fake devices -> 4).

Simulates losing half the fleet mid-job: train 3 steps on a (2,2,2) mesh,
checkpoint, rebuild a (2,2) mesh from 4 surviving devices, elastic-restore
(re-shard every leaf), and run 2 more steps.  The loss trajectory after the
re-shard must continue exactly (global batch preserved; checkpoints are
mesh-agnostic full-logical arrays) — compared against an uninterrupted
8-device run of the same 5 steps.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = textwrap.dedent(
    """
    import os, sys, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp

    from repro.checkpoint import CheckpointManager
    from repro.configs import get_arch
    from repro.launch.mesh import make_mesh
    from repro.data import DataConfig, SyntheticLMDataset
    from repro.launch.train import build_train_step
    from repro.models.model import Model
    from repro.models.transformer import ModelOptions
    from repro.optim import AdamWConfig, adamw_init
    from repro.parallel.sharding import activation_mesh, batch_specs, param_specs

    ckpt_dir = sys.argv[1]
    cfg = get_arch("qwen1.5-0.5b").reduced()
    model = Model(cfg, ModelOptions())
    ocfg = AdamWConfig(lr=1e-3)
    ds = SyntheticLMDataset(DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=8, seed=0))
    step_fn = build_train_step(model, ocfg, total_steps=5, warmup=1)

    def opt_shardings(mesh):
        shapes = jax.eval_shape(adamw_init, model.param_shapes())
        return {
            "m": param_specs(shapes["m"], mesh),
            "v": param_specs(shapes["v"], mesh),
            "step": jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
        }

    def run_steps(mesh, params, opt, steps):
        p_sh = param_specs(model.param_shapes(), mesh)
        o_sh = opt_shardings(mesh)
        jit_step = jax.jit(step_fn, in_shardings=(p_sh, o_sh, None),
                           out_shardings=(p_sh, o_sh, None))
        losses = []
        for s in steps:
            batch = {"tokens": jnp.asarray(ds.batch_at(s)["tokens"])}
            b_sh = batch_specs(batch, mesh)
            batch = jax.tree.map(lambda a, sh: jax.device_put(a, sh), batch, b_sh)
            with mesh, activation_mesh(mesh):
                params, opt, m = jit_step(params, opt, batch)
            losses.append(float(m["loss"]))
        return params, opt, losses

    # --- uninterrupted 8-device reference run (5 steps) ---
    mesh8 = make_mesh((2, 2, 2), ("pod", "data", "model"))
    p_sh8 = param_specs(model.param_shapes(), mesh8)
    with mesh8, activation_mesh(mesh8):
        params0 = jax.jit(model.init, out_shardings=p_sh8)(jax.random.PRNGKey(0))
        opt0 = adamw_init(params0)
    _, _, ref_losses = run_steps(mesh8, params0, opt0, range(5))

    # --- elastic run: 3 steps on 8 devices, checkpoint, resume on 4 ---
    with mesh8, activation_mesh(mesh8):
        params = jax.jit(model.init, out_shardings=p_sh8)(jax.random.PRNGKey(0))
        opt = adamw_init(params)
    params, opt, losses_a = run_steps(mesh8, params, opt, range(3))
    mgr = CheckpointManager(ckpt_dir, async_write=False)
    mgr.save(2, {"params": params, "opt": opt})

    # "pod loss": rebuild on the first 4 devices only
    mesh4 = make_mesh((2, 2), ("data", "model"), devices=jax.devices()[:4])
    template = {"params": model.param_shapes(),
                "opt": jax.eval_shape(adamw_init, model.param_shapes())}
    shardings = {"params": param_specs(template["params"], mesh4),
                 "opt": opt_shardings(mesh4)}
    restored, _ = mgr.restore(2, template, shardings=shardings)
    params4, opt4 = restored["params"], restored["opt"]
    assert all(len(l.sharding.mesh.devices.flatten()) == 4
               for l in jax.tree.leaves(params4))
    _, _, losses_b = run_steps(mesh4, params4, opt4, range(3, 5))

    print(json.dumps({"ref": ref_losses, "elastic": losses_a + losses_b}))
    """
)


@pytest.mark.slow
def test_elastic_8_to_4_devices(tmp_path):
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT, str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert len(rec["ref"]) == len(rec["elastic"]) == 5
    # pre-reshard steps are bit-identical; post-reshard steps agree to float
    # reduction-order noise (4-device collectives group sums differently
    # than 8-device ones — non-associative fp add, not an optimization drift)
    for a, b in zip(rec["ref"][:3], rec["elastic"][:3]):
        assert a == b, (rec["ref"], rec["elastic"])
    for a, b in zip(rec["ref"][3:], rec["elastic"][3:]):
        assert abs(a - b) < 1e-3, (rec["ref"], rec["elastic"])
