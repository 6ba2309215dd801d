"""Compile rehearsal of a cell's programs for a described TPU v5e, with no
chip: the fused decode (``chunk_steps`` steps) and the widest chunked
prefill the cell can plan, each with ``memory_analysis()``.  This is what
sizes ``max_slots`` and ``kv_pool_blocks`` in ``bench/workloads/``.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py --workload <cell> [--max-slots n]

Nothing runs: the numbers are the compiler's, not a chip's.
"""
import argparse
import dataclasses
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--max-slots", type=int)
    ap.add_argument("--rows", type=int, help="prefill rows (default: max_slots)")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import system, weights
    from bench.spec import load_cell
    from repro.models.attention import BlockTables
    from repro.models.model import Model
    from repro.serve.decode_loop import make_fused_decode
    from repro.serve.prefill import _drop_free, _suffix_jit
    from repro.serve.sampling import GREEDY

    # this process is on the CPU backend, which would pick interpret mode;
    # the chip compiles the kernels, so compile them here too
    import repro.kernels.paged_attention.kernel as paged_kernel
    paged_kernel.interpret_mode = lambda interpret=None: bool(interpret)

    cell = load_cell(args.workload)
    eng = dict(cell.workload["engine"])
    bs = cell.config["serving"]["kv_block_size"]
    w = -(-eng["max_len"] // bs)
    if args.max_slots:
        eng["max_slots"] = args.max_slots
        eng["kv_pool_blocks"] = 1 + args.max_slots * w
    b, nblk = eng["max_slots"], eng["kv_pool_blocks"]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])
    put = lambda t: jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=dev), t)
    model = Model(system.arch(cell.family, cell.config))
    model = dataclasses.replace(model, opts=dataclasses.replace(
        model.opts, attn_impl=cell.config["serving"]["attn_impl"]))
    params = put(weights.shapes(cell.family, cell.config))
    states = put(jax.eval_shape(lambda: model.init_decode_state(b, eng["max_len"], paged=(nblk, bs))))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=dev)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=dev)
    tables = BlockTables(i32(b, w), i32())
    steps = cell.config["serving"]["chunk_steps"]
    dec = make_fused_decode(model).lower(params, i32(b, 1), states, i32(b), key, steps=steps,
                                         sampler=GREEDY, tables=tables).compile()
    rows = args.rows or b
    width = cell.config["serving"]["prefill_chunk_tokens"]
    pre = _suffix_jit(_drop_free(model)).lower(params, i32(rows, width), i32(rows), states,
                                               i32(rows, w), i32(rows), ctx_blocks=w).compile()
    for name, c in (("fused decode", dec), (f"prefill [{rows}, {width}] ctx {w}", pre)):
        m = c.memory_analysis()
        gib = lambda x: x / 2**30
        print(f"{cell.name} slots {b} pool {nblk}: {name}: args {gib(m.argument_size_in_bytes):.2f} GiB "
              f"out {gib(m.output_size_in_bytes):.2f} GiB temp {gib(m.temp_size_in_bytes):.2f} GiB "
              f"alias {gib(m.alias_size_in_bytes):.2f} GiB "
              f"total {gib(m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes - m.alias_size_in_bytes):.2f} GiB; "
              f"kernel {'tpu_custom_call' in c.as_text()}", flush=True)


if __name__ == "__main__":
    main()
