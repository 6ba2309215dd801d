"""Multi-device sharding integration test (subprocess: 8 fake CPU devices).

The 512-device production dry-run runs out-of-process (launch/dryrun.py);
this test pins the same machinery — sharding rules, step builders,
collective parsing — on an 8-device (2,2,2) mesh with a tiny config, so a
sharding regression fails CI in seconds rather than at pod-launch time.
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
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp

    from repro.configs import get_arch
    from repro.launch.mesh import make_mesh
    from repro.launch.dryrun import parse_collectives
    from repro.models.model import Model, input_specs
    from repro.models.transformer import ModelOptions
    from repro.optim import AdamWConfig, adamw_init, adamw_update
    from repro.parallel.sharding import activation_mesh, batch_specs, param_specs

    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    cfg = get_arch("qwen3-moe-30b-a3b").reduced()  # MoE: exercises EP + FSDP + TP
    model = Model(cfg, ModelOptions())
    param_shapes = model.param_shapes()
    p_shard = param_specs(param_shapes, mesh)
    opt_shapes = jax.eval_shape(adamw_init, param_shapes)
    o_shard = {
        "m": param_specs(opt_shapes["m"], mesh),
        "v": param_specs(opt_shapes["v"], mesh),
        "step": jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
    }
    specs = {"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32)}
    b_shard = batch_specs(specs, mesh)
    ocfg = AdamWConfig()

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(lambda p: model.loss(p, batch)[0])(params)
        p2, o2, stats = adamw_update(params, grads, opt_state, ocfg)
        return p2, o2, {"loss": loss, **stats}

    fn = jax.jit(train_step, in_shardings=(p_shard, o_shard, b_shard),
                 out_shardings=(p_shard, o_shard, None))
    with mesh, activation_mesh(mesh):
        lowered = fn.lower(param_shapes, opt_shapes, specs)
        compiled = lowered.compile()
        # actually execute on the 8 fake devices — numerics + shardings real
        params = jax.jit(model.init, out_shardings=p_shard)(jax.random.PRNGKey(0))
        opt = adamw_init(params)
        tokens = jax.device_put(
            jax.random.randint(jax.random.PRNGKey(1), (8, 64), 0, cfg.vocab), b_shard["tokens"])
        p2, o2, stats = fn(params, opt, {"tokens": tokens})

    coll = parse_collectives(compiled.as_text())
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    print(json.dumps({
        "loss": float(stats["loss"]),
        "collectives": sorted(coll),
        "flops": float(dict(ca).get("flops", 0.0)),
        "n_devices": jax.device_count(),
    }))
    """
)


@pytest.mark.slow
def test_train_step_shards_on_8_devices():
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], env=env, capture_output=True, text=True, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["n_devices"] == 8
    assert rec["flops"] > 0
    import math
    assert math.isfinite(rec["loss"]) and 0 < rec["loss"] < 20
    # FSDP + TP must produce real collectives in the step
    assert "all-reduce" in rec["collectives"]
    assert "all-gather" in rec["collectives"]
