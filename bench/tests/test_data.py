"""The on-device generator keeps the semantics of
``repro.data.synthetic.make_image_dataset``, makes the fleet block by block
as one ``lax.map`` over the blocks would, and makes a sharded fleet's data
on the devices that hold it."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import data as data_mod
from repro.data.synthetic import make_image_dataset

DATASET = {"classes": 10, "image_size": 28, "channels": 1, "test": 2000,
           "difficulty": 1.6}


def _stats(x, y, classes=10):
    """Per-class share of the labels, and the share of the pixel variance
    that the class means explain (how separable the classes are)."""
    x = np.asarray(x, np.float64).reshape(len(y), -1)
    y = np.asarray(y)
    share = np.bincount(y, minlength=classes) / len(y)
    means = np.stack([x[y == c].mean(0) for c in range(classes)])
    between = np.mean(((means[y] - x.mean(0)) ** 2).sum(1))
    return share, between / ((x - x.mean(0)) ** 2).sum(1).mean()


@pytest.fixture(scope="module")
def made():
    return data_mod.make(jax.random.PRNGKey(3), DATASET, n_clients=400,
                         per_client=10)


def test_shapes_types_and_label_range(made):
    assert made.x.shape == (400, 10, 28, 28, 1) and made.x.dtype == np.float32
    assert made.y.shape == (400, 10) and made.y.dtype == np.int32
    assert made.test_x.shape == (2000, 28, 28, 1)
    for y in (made.y, made.test_y):
        assert int(y.min()) >= 0 and int(y.max()) == 9


def test_standardized_like_the_host_generator(made):
    for x in (made.x, made.test_x):
        assert abs(float(x.mean())) < 1e-3
        assert abs(float(x.std()) - 1.0) < 1e-3


@pytest.mark.parametrize("seed", [0, 1])
def test_per_class_statistics_match_the_host_generator(made, seed):
    train, _ = make_image_dataset("mnist", 10, 28, 1, 4000, 10, seed=seed)
    share_h, sep_h = _stats(train.images, train.labels)
    share_d, sep_d = _stats(made.x.reshape(-1, 28, 28, 1), made.y.reshape(-1))
    assert np.all(np.abs(share_d - 0.1) < 0.03)
    assert np.all(np.abs(share_h - 0.1) < 0.03)
    # the prototypes are random per seed, so the separability varies
    # from draw to draw by ~30%; a generator that lost the prototype
    # mixing, the roll or the noise scale moves it by several times that
    assert 0.5 < sep_d / sep_h < 2.0


def test_same_seed_same_data():
    a = data_mod.make(jax.random.PRNGKey(9), DATASET, 40, 3)
    b = data_mod.make(jax.random.PRNGKey(9), DATASET, 40, 3)
    np.testing.assert_array_equal(np.asarray(a.x), np.asarray(b.x))
    c = data_mod.make(jax.random.PRNGKey(10), DATASET, 40, 3)
    assert not np.array_equal(np.asarray(a.x), np.asarray(c.x))


def test_sharded_fleet_is_made_in_place_on_four_devices():
    code = textwrap.dedent("""
        import jax, numpy as np
        from bench import data as d
        got = d.make(jax.random.PRNGKey(0), %r, n_clients=256, per_client=2,
                     shards=4)
        for arr in (got.x, got.y):
            shards = arr.addressable_shards
            assert len({s.device for s in shards}) == 4
            assert all(s.data.shape[0] == 64 for s in shards), [s.data.shape for s in shards]
        x = np.asarray(got.x)
        assert abs(x.mean()) < 1e-3 and abs(x.std() - 1) < 1e-3
        # each device made its own clients: no two blocks are equal
        assert not np.array_equal(x[:64], x[64:128])
        print("ok")
    """ % DATASET)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.pathsep.join(sys.path)}
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0 and out.stdout.strip().endswith("ok"), out.stderr[-2000:]


def _stacked(key, protos, clients, per_client, size, difficulty):
    """The blocks of ``bench.data._clients`` stacked by one ``lax.map`` in
    one program (on a TPU the stack takes a padded layout several times
    the fleet's size)."""
    block = max(d for d in range(1, min(max(
        data_mod.BLOCK_IMAGES // per_client, 1), clients) + 1)
        if clients % d == 0)

    def one(i):
        x, y = data_mod.images(jax.random.fold_in(key, i), protos,
                               block * per_client, size, difficulty)
        return (x.reshape((block, per_client) + x.shape[1:]),
                y.reshape(block, per_client))

    x, y = jax.lax.map(one, jnp.arange(clients // block))
    return x.reshape((clients,) + x.shape[2:]), y.reshape(clients, per_client)


def test_blocks_joined_equal_the_stacked_blocks_to_the_bit(monkeypatch):
    # 8 blocks of 512 clients at 4 examples a client
    monkeypatch.setattr(data_mod, "BLOCK_IMAGES", 2048)
    key = jax.random.PRNGKey(5)
    protos = data_mod.prototypes(jax.random.fold_in(key, 0), 10, 1)
    args = (jax.random.fold_in(key, 1), protos, 4096, 4, 28, 1.6)
    x, y = data_mod._clients(*args)
    sx, sy = jax.jit(_stacked, static_argnums=(2, 3, 4, 5))(*args)
    assert x.shape == (4096, 4, 28, 28, 1) and y.shape == (4096, 4)
    np.testing.assert_array_equal(np.asarray(x), np.asarray(sx))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(sy))
