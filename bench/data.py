"""Synthetic image data, made on the device from a seed.

The semantics are those of ``repro.data.synthetic.make_image_dataset``:
each class is a mixture of three smooth prototypes (a 4x4 random field
repeated up to the image size); a sample mixes its class's prototypes
with Dirichlet(1, 1, 1) weights, rolls the image by a shift in [-2, 2] on
both axes, adds ``difficulty`` times unit Gaussian noise, and the whole
set is standardized by its own mean and standard deviation. Labels are
uniform over the classes. Clients hold i.i.d. samples, as the IID
partition gives them.

Every draw is a ``jax.random`` call in a jitted program that makes one
block of clients, so a fleet of millions of clients is made in seconds
and never passes through the host. Across several devices each device makes its own
block of clients (``shard_map``), so the fleet's data never sits whole on
one device.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

PROTOS_PER_CLASS = 3
FREQ = 4  # prototype fields are FREQ x FREQ before they are repeated
# images made per block of one program: bounds the block's temporaries
BLOCK_IMAGES = 131072
FLEET_AXIS = "fleet"


@dataclasses.dataclass(frozen=True)
class ImageData:
    x: jax.Array  # (n_clients, per_client, H, W, C) float32, standardized
    y: jax.Array  # (n_clients, per_client) int32
    test_x: jax.Array  # (test, H, W, C) float32, standardized
    test_y: jax.Array  # (test,) int32

    @property
    def test(self):
        """The test set as ``paper_cnn.eval_loss`` reads it."""
        return self.test_x, self.test_y


def prototypes(key, classes: int, channels: int) -> jax.Array:
    return jax.random.normal(
        key, (classes, PROTOS_PER_CLASS, FREQ, FREQ, channels), jnp.float32)


def images(key, protos, n: int, size: int, difficulty: float):
    """``n`` samples before standardization: (images (n, H, W, C), labels)."""
    k_lab, k_mix, k_shift, k_noise = jax.random.split(key, 4)
    labels = jax.random.randint(k_lab, (n,), 0, protos.shape[0], jnp.int32)
    mix = jax.random.dirichlet(k_mix, jnp.ones((PROTOS_PER_CLASS,)), (n,))
    base = jnp.einsum("np,npijc->nijc", mix, protos[labels],
                      precision=jax.lax.Precision.HIGHEST)  # (n, 4, 4, C)
    shift = jax.random.randint(k_shift, (n, 2), -2, 3)
    # np.roll by s: out[h] = in[(h - s) mod size]; in[h] is field[h // reps]
    pix = jnp.arange(size)
    reps = size // FREQ
    rows = ((pix[None, :] - shift[:, :1]) % size) // reps  # (n, size)
    cols = ((pix[None, :] - shift[:, 1:]) % size) // reps
    img = base[jnp.arange(n)[:, None, None], rows[:, :, None],
               cols[:, None, :]]
    img = img + difficulty * jax.random.normal(k_noise, img.shape, jnp.float32)
    return img, labels


def _clients(key, protos, clients: int, per_client: int, size: int,
             difficulty: float):
    """``clients`` clients' samples, made block by block (block ``i`` from
    ``fold_in(key, i)``) and joined by one concatenate. Outside ``jit``
    each block is a jitted call of its own: stacked inside one program,
    the blocks take a padded TPU layout several times the fleet's size."""
    target = max(BLOCK_IMAGES // per_client, 1)
    block = max(d for d in range(1, min(target, clients) + 1)
                if clients % d == 0)
    one = _block(block, per_client, size, difficulty)
    xs, ys = zip(*(one(jax.random.fold_in(key, i), protos)
                   for i in range(clients // block)))
    return jnp.concatenate(xs), jnp.concatenate(ys)


def _block(block: int, per_client: int, size: int, difficulty: float):
    """One block of ``block`` clients: ``(key, protos) -> (x, y)``."""

    @jax.jit
    def one(key, protos):
        x, y = images(key, protos, block * per_client, size, difficulty)
        return (x.reshape((block, per_client) + x.shape[1:]),
                y.reshape(block, per_client))

    return one


@jax.jit
def _moments(x):
    mean = jnp.mean(x)
    return mean, jnp.sqrt(jnp.mean(jnp.square(x - mean)))


@functools.partial(jax.jit, donate_argnums=0)
def _standardize(x, mean, std):
    return (x - mean) / (std + 1e-6)


def standardize(x):
    """The set divided by its own moments, in place of ``x``."""
    mean, std = _moments(x)
    return _standardize(x, mean, std)


def fleet_mesh(shards: int) -> Mesh:
    """The 1-D mesh the fleet-sharded engine builds: the first ``shards``
    devices over the ``fleet`` axis."""
    return Mesh(np.asarray(jax.devices()[:shards]), (FLEET_AXIS,))


def make(seed_key, dataset: dict, n_clients: int, per_client: int,
         shards: int = 1) -> ImageData:
    """The cell's data from ``seed_key``. ``dataset`` holds ``classes``,
    ``image_size``, ``channels``, ``test`` and ``difficulty``. With
    ``shards`` > 1 the client axis is laid out over ``fleet_mesh(shards)``
    and each device makes its own clients."""
    size, ch = int(dataset["image_size"]), int(dataset["channels"])
    difficulty = float(dataset["difficulty"])
    protos = prototypes(jax.random.fold_in(seed_key, 0),
                        int(dataset["classes"]), ch)
    k_train = jax.random.fold_in(seed_key, 1)
    if shards == 1:
        gen = functools.partial(
            _clients, clients=n_clients, per_client=per_client, size=size,
            difficulty=difficulty)
    else:
        if n_clients % shards:
            raise ValueError(f"{shards} shards do not divide {n_clients} "
                             "clients")

        def local(key, protos):
            key = jax.random.fold_in(key, jax.lax.axis_index(FLEET_AXIS))
            return _clients(key, protos, n_clients // shards, per_client,
                            size, difficulty)

        gen = jax.jit(jax.shard_map(
            local, mesh=fleet_mesh(shards), in_specs=(P(), P()),
            out_specs=(P(FLEET_AXIS), P(FLEET_AXIS))))
    x, y = gen(k_train, protos)
    tx, ty = jax.jit(functools.partial(
        images, n=int(dataset["test"]), size=size, difficulty=difficulty))(
            jax.random.fold_in(seed_key, 2), protos)
    return ImageData(standardize(x), y, standardize(tx), ty)
