"""The main path's Pallas kernels compile for a TPU v5e at real sizes.

Nothing runs: the TPU compiler, which is installed with jax, compiles
for a v5e that is described, not attached, and refuses what the chip
would refuse (unaligned blocks, scalar stores to VMEM, too much VMEM).
Interpret mode on the CPU cannot catch those. The topology is described
inside a fixture, never at import, so every test worker collects the
same tests and only the one that runs this file loads the TPU library.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.paper_cnn import CNN_CONFIGS
from repro.kernels import event_topk, fedavg_reduce
from repro.models import cnn as cnn_mod


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    own_log_dir = "TPU_LOG_DIR" not in os.environ
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the cache
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)
    compilation_cache.reset_cache()
    if own_log_dir:
        del os.environ["TPU_LOG_DIR"]


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("n,k", [
    (262144, 256),  # 4 tiles of DEFAULT_BLOCK_N
    (262144, 2621),  # the 1% buffer of a 262,144-client fleet
    (100000, 15),  # n not a multiple of 128, k of the paper's cohort
])
def test_event_topk_compiles_for_v5e(one_chip, n, k):
    times = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    text = _compiled_text(
        lambda t: event_topk.tile_next_k(t, k=k)[0][:, :k], times)
    assert "tpu_custom_call" in text


def test_fedavg_reduce_compiles_at_paper_cnn_width(one_chip):
    cfg = CNN_CONFIGS["paper-cnn-mnist"]
    p_sds = jax.eval_shape(
        lambda: cnn_mod.init_params(jax.random.PRNGKey(0), cfg))
    n_params = sum(math.prod(x.shape) for x in jax.tree.leaves(p_sds))
    cohort = 15
    params = jax.ShapeDtypeStruct((cohort, n_params), jnp.float32,
                                  sharding=one_chip)
    weights = jax.ShapeDtypeStruct((cohort,), jnp.float32, sharding=one_chip)
    text = _compiled_text(fedavg_reduce.fedavg_reduce, params, weights)
    assert "tpu_custom_call" in text


def _while_bodies(text):
    """The HLO text of every ``while`` loop body in a compiled module."""
    bodies = set(re.findall(r"\bbody=%?([\w.\-]+)", text))
    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif name is not None:
            comps[name].append(line)
    return {b: "\n".join(comps.get(b, ())) for b in bodies}


def test_async_chunk_gathers_client_rows_inside_the_loop(one_chip):
    """The async chunk's per-step cohort gather reads client rows where
    they lie: no copy of the whole fleet's examples runs inside the scan,
    and the chunk's temporaries stay below the examples' own bytes (the
    padded relayout of 28x28 images is what they would otherwise cost).
    The fleet is large enough that the images' bytes outweigh the
    temporaries every fleet size pays (the cohort's local training)."""
    import dataclasses

    import numpy as np

    from repro.data.synthetic import ImageDataset
    from repro.engine import RunConfig, make_engine
    from repro.fl.task import make_cnn_task

    n, epc, side = 65536, 4, 28
    cnn = CNN_CONFIGS["paper-cnn-mnist"]
    one = ImageDataset(cnn.name, np.zeros((1, side, side, 1), np.float32),
                       np.zeros((1,), np.int32))
    task = make_cnn_task(cnn, one, one, n_clients=1)
    x = jax.ShapeDtypeStruct((n, epc, side, side, 1), jnp.float32,
                             sharding=one_chip)
    y = jax.ShapeDtypeStruct((n, epc), jnp.int32, sharding=one_chip)
    task = dataclasses.replace(task, client_data={"x": x, "y": y},
                               examples_per_client=epc)
    cfg = RunConfig(mode="async", n_clients=n, k=n * 15 // 100, m=10,
                    aggregator="fedbuff", buffer_size=10, local_epochs=5,
                    batch_size=50, steps_per_chunk=8, eval_every=8,
                    collect_history=False, use_kernel=True, rounds=8)
    engine = make_engine(task, cfg)
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        engine.init())
    r0 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    runner = engine._chunk
    compiled = runner._fn(8, False).lower(state, runner._data, r0).compile()

    fleet = n * epc * side * side
    bodies = _while_bodies(compiled.as_text())
    assert bodies
    for name, body in bodies.items():
        for m in re.finditer(r"= \w+\[([\d,]*)\][^\n]*? copy(?:-start)?\(",
                             body):
            elems = math.prod(int(d) for d in m.group(1).split(",") if d)
            assert elems < fleet, f"{name} copies {elems} elements: {m[0]}"
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < fleet * 4, f"temporaries {temp} B"
