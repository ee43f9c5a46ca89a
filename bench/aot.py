#!/usr/bin/env python3
"""Compiles a cell's chunk and eval programs for a described TPU v5e at the
cell's real sizes, with no chip attached, and prints each program's
memory per device.

    JAX_PLATFORMS=cpu python bench/aot.py --workload <cell>

Nothing runs: the TPU compiler installed with jax compiles for a v5e 2x2
that is described, and refuses what the chip would refuse (a program that
does not fit, a kernel that cannot be partitioned). The client data is
given as shapes only, never made. The process sees the CPU backend, so
the pop is told to use its kernel (``use_kernel=True``) and the kernel to
compile for the TPU, as the chip would pick.
"""
import argparse
import dataclasses
import importlib
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    from bench import data as data_mod
    from bench.cell import load_cell
    from repro.engine import make_engine
    from repro.engine import sharded as sharded_mod
    from repro.kernels import ops

    jax.config.update("jax_enable_compilation_cache", False)
    cell = load_cell(args.workload)
    conf = cell.config
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    devices = topo.devices[:cell.chips]
    ops._interpret = lambda: False
    n = conf["run"]["n_clients"]
    epc = conf["dataset"]["examples_per_client"]
    size, ch = conf["dataset"]["image_size"], conf["dataset"]["channels"]
    shards = conf["run"].get("mesh_shards") or 1

    if shards > 1:
        mesh = Mesh(devices, (data_mod.FLEET_AXIS,))
        client = NamedSharding(mesh, P(data_mod.FLEET_AXIS))
        rep = NamedSharding(mesh, P())

        def place(tree, sharding):
            if isinstance(sharding, jax.sharding.Sharding):
                sharding = jax.tree.map(lambda _: sharding, tree)
            return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=s), tree, sharding)

        # the sharded engine places its data and state: give it shapes
        jax.device_put = place
    else:
        client = rep = SingleDeviceSharding(devices[0])
    x = jax.ShapeDtypeStruct((n, epc, size, size, ch), jnp.float32, sharding=client)
    y = jax.ShapeDtypeStruct((n, epc), jnp.int32, sharding=client)
    test = data_mod.ImageData(x=x, y=y, test_x=jnp.zeros(
        (conf["dataset"]["test"], size, size, ch)), test_y=jnp.zeros(
        (conf["dataset"]["test"],), jnp.int32))
    model = importlib.import_module(f"bench.models.{conf['model']}")
    task = model.build_task(conf, test)
    cfg = cell.run_config(0, cell.period)
    if cfg.mode == "async" and shards == 1:
        cfg = dataclasses.replace(cfg, use_kernel=True)
    if shards > 1:
        engine = sharded_mod.ShardedAsyncEngine(task, cfg, mesh=mesh)
    else:
        engine = make_engine(task, cfg)
    state = engine.init()

    def shapes(tree, sharding):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=getattr(a, "sharding", None)
            if shards > 1 else sharding), tree)

    state = shapes(state, rep)
    steps = cfg.resolved_steps_per_chunk()
    hist = bool(cfg.collect_history)
    chunk = engine._chunk._fn(steps, hist).lower(
        state, {"x": x, "y": y},
        jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)).compile()
    tx = jax.ShapeDtypeStruct(test.test_x.shape, jnp.float32, sharding=rep)
    ty = jax.ShapeDtypeStruct(test.test_y.shape, jnp.int32, sharding=rep)
    eval_fn = task.eval_fn.func.lower(state["params"], tx, ty).compile()
    for name, c in (("chunk", chunk), ("eval", eval_fn)):
        m = c.memory_analysis()
        text = c.as_text()
        print(f"{cell.name} {name}: arguments {m.argument_size_in_bytes / 1e9:.3f} GB, "
              f"outputs {m.output_size_in_bytes / 1e9:.3f} GB, temporaries "
              f"{m.temp_size_in_bytes / 1e9:.3f} GB, aliased "
              f"{m.alias_size_in_bytes / 1e9:.3f} GB per device; "
              f"tpu_custom_call: {'tpu_custom_call' in text}; all-gather ops: "
              f"{text.count('all-gather(')}, all-reduce ops: {text.count('all-reduce(')}",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
