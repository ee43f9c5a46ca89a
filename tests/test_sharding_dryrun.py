"""Distribution tests: sharding rules produce valid specs, and a reduced
arch lowers+compiles on a multi-device (forced host device) mesh with the
production rules — run in a subprocess because device count is fixed at
first jax init and the rest of the suite must see 1 device."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs import all_archs
from repro.models import factory
from repro import sharding as sr

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_param_pspecs_cover_all_leaves():
    from jax.sharding import PartitionSpec as P

    class FakeMesh:
        shape = {"data": 16, "model": 16}

    for name in ("tinyllama-1.1b", "deepseek-v2-236b", "jamba-v0.1-52b", "whisper-tiny"):
        cfg = all_archs()[name]  # FULL config: specs only, no allocation
        model = factory.build(cfg)
        p_sds = jax.eval_shape(lambda m=model: m.init(jax.random.PRNGKey(0)))
        specs = sr.params_pspecs(p_sds, FakeMesh())
        leaves_p = jax.tree_util.tree_leaves(p_sds)
        leaves_s = jax.tree_util.tree_leaves(specs, is_leaf=lambda x: isinstance(x, P))
        assert len(leaves_p) == len(leaves_s)
        # every sharded axis divides
        for leaf, spec in zip(leaves_p, leaves_s):
            for dim, ax in zip(leaf.shape, tuple(spec)):
                if ax is None:
                    continue
                size = 16 if not isinstance(ax, tuple) else 16 ** len(ax)
                assert dim % size == 0, (name, leaf.shape, spec)


def test_big_params_actually_sharded():
    """Anything > 8M params must shard on at least one axis (fits HBM)."""
    from jax.sharding import PartitionSpec as P

    class FakeMesh:
        shape = {"data": 16, "model": 16}

    cfg = all_archs()["llama4-maverick-400b-a17b"]
    model = factory.build(cfg)
    p_sds = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    specs = sr.params_pspecs(p_sds, FakeMesh())
    for leaf, spec in zip(
        jax.tree_util.tree_leaves(p_sds),
        jax.tree_util.tree_leaves(specs, is_leaf=lambda x: isinstance(x, P)),
    ):
        if leaf.size > 8_000_000:
            assert any(ax is not None for ax in tuple(spec)), (leaf.shape, spec)


_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import jax, jax.numpy as jnp, json
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_arch, INPUT_SHAPES
from repro.models import factory, pshard
from repro import sharding as sr
import dataclasses

from jax.sharding import AxisType
mesh = jax.make_mesh((4, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
cfg = get_arch("jamba-v0.1-52b").reduced()
cfg = dataclasses.replace(cfg, d_model=256, vocab_size=512)
model = factory.build(cfg)
shape = dataclasses.replace(INPUT_SHAPES["train_4k"], seq_len=128, global_batch=8)
specs = factory.input_specs(cfg, shape)
p_sds = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
named = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t, is_leaf=lambda x: isinstance(x, P))
j = jax.jit(model.sgd_train_step,
            in_shardings=(named(sr.params_pspecs(p_sds, mesh)), named(sr.batch_pspecs(specs, mesh)), None),
            out_shardings=(named(sr.params_pspecs(p_sds, mesh)), None))
with mesh, pshard.mesh_context(mesh):
    compiled = j.lower(p_sds, specs, jax.ShapeDtypeStruct((), jnp.float32)).compile()
text = compiled.as_text()
has_coll = any(k in text for k in ("all-reduce", "all-gather", "reduce-scatter"))
# ALSO actually execute on the 16 fake devices with real values
params = jax.device_put(model.init(jax.random.PRNGKey(0)), named(sr.params_pspecs(p_sds, mesh)))
batch = factory.synth_batch(jax.random.PRNGKey(1), cfg, 8, 128)
with mesh, pshard.mesh_context(mesh):
    new_params, metrics = j(params, batch, jnp.asarray(0.01, jnp.float32))
loss = float(metrics["total_loss"])
print(json.dumps({"ok": True, "has_collectives": has_coll, "loss": loss}))
"""


def test_sharded_train_step_16_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-c", _SUBPROC], capture_output=True, text=True, env=env,
        timeout=540,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["ok"] and res["has_collectives"]
    assert np.isfinite(res["loss"])
