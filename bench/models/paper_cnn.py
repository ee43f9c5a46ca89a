"""The paper's CNN (McMahan et al., arXiv 1602.05629): two 5x5 SAME
convolutions (32, 64 channels), each followed by ReLU and a 2x2 max-pool,
a 512-wide ReLU layer and the class logits, on MNIST-shaped images.

The configuration's ``widths`` size the CNN and its ``dataset`` the
images (``bench/data.py``). ``build_task`` hands the program its own CNN
task with the benchmark's on-device data in place of the host-built
client data. The reference's CNN (``init``, ``loss``, ``eval_loss``)
imports nothing of the program; its products take JAX's default
precision, as the program's do. The FLOP
counts are the work the algorithm needs for one example, counted from
the widths: multiply-adds of the convolutions (only the taps inside the
image) and of the dense layers, two FLOPs each; bias, ReLU, pooling and
softmax are left out (under 1% here).
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp

from bench import data as images

EVAL_BATCH = 500  # fl/task's eval batch


def make_data(key, config: dict, shards: int = 1) -> images.ImageData:
    dataset = config["dataset"]
    return images.make(key, dataset, config["run"]["n_clients"],
                       dataset["examples_per_client"], shards)


def build_task(config: dict, data):
    """The program's ``make_cnn_task`` for this configuration, with
    ``client_data`` and ``examples_per_client`` replaced by ``data``'s
    on-device arrays (``make_cnn_task`` builds client data on the host)."""
    from repro.configs.paper_cnn import CNNConfig
    from repro.data.synthetic import ImageDataset
    from repro.fl.task import make_cnn_task

    w = config["widths"]
    cnn = CNNConfig(
        name=config["name"], image_size=w["image_size"],
        channels=w["channels"], num_classes=w["num_classes"],
        conv_channels=tuple(w["conv_channels"]), kernel=w["kernel"],
        fc_width=w["fc_width"])
    test = ImageDataset(cnn.name, data.test_x, data.test_y)
    one = ImageDataset(cnn.name, data.test_x[:1], data.test_y[:1])
    task = make_cnn_task(cnn, one, test, n_clients=1)
    return dataclasses.replace(
        task, client_data={"x": data.x, "y": data.y},
        examples_per_client=int(data.x.shape[1]))


# ---------------------------------------------------------------- reference


def cnn_init(key, w: dict, dtype) -> Dict:
    """He-normal weights, zero biases (McMahan et al.'s CNN)."""
    ks = jax.random.split(key, 4)
    c1, c2 = w["conv_channels"]
    kk, s = w["kernel"], w["image_size"] // 4
    flat = s * s * c2

    def he(k, shape, fan_in):
        return jax.random.normal(k, shape) * (2.0 / fan_in) ** 0.5

    p = {
        "conv1": {"w": he(ks[0], (kk, kk, w["channels"], c1),
                          kk * kk * w["channels"]), "b": jnp.zeros((c1,))},
        "conv2": {"w": he(ks[1], (kk, kk, c1, c2), kk * kk * c1),
                  "b": jnp.zeros((c2,))},
        "fc1": {"w": he(ks[2], (flat, w["fc_width"]), flat),
                "b": jnp.zeros((w["fc_width"],))},
        "fc2": {"w": he(ks[3], (w["fc_width"], w["num_classes"]),
                        w["fc_width"]), "b": jnp.zeros((w["num_classes"],))},
    }
    return jax.tree.map(lambda a: a.astype(dtype), p)


def cnn_forward(p, x):
    def conv(x, q):
        y = jax.lax.conv_general_dilated(
            x, q["w"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return y + q["b"]

    def pool(x):
        return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                     (1, 2, 2, 1), "VALID")

    x = pool(jax.nn.relu(conv(x, p["conv1"])))
    x = pool(jax.nn.relu(conv(x, p["conv2"])))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(jnp.dot(x, p["fc1"]["w"])
                    + p["fc1"]["b"])
    return jnp.dot(x, p["fc2"]["w"]) + p["fc2"]["b"]


def xent(p, x, y):
    logp = jax.nn.log_softmax(cnn_forward(p, x))
    return -jnp.take_along_axis(logp, y[:, None], axis=-1).mean()


def evaluate(p, tx, ty, batch: int = EVAL_BATCH):
    """Mean loss over the test set in batches of ``batch`` (a last
    partial batch is left out)."""
    bs = min(batch, tx.shape[0])
    nb = max(tx.shape[0] // bs, 1)
    xb = tx[:nb * bs].reshape((nb, bs) + tx.shape[1:])
    yb = ty[:nb * bs].reshape(nb, bs)

    def one(carry, b):
        logp = jax.nn.log_softmax(cnn_forward(p, b[0]).astype(jnp.float32))
        return carry - jnp.take_along_axis(logp, b[1][:, None], axis=-1).sum(), None

    total, _ = jax.lax.scan(one, jnp.zeros((), jnp.float32), (xb, yb))
    return total / (nb * bs)


def init(key, config: dict, dtype) -> Dict:
    return cnn_init(key, config["widths"], dtype)


loss = xent


def eval_loss(p, test):
    return evaluate(p, *test)


# ---------------------------------------------------------------- counts


def eval_examples(data) -> int:
    """Test examples one eval runs forward: whole batches of 500."""
    n = data.test_x.shape[0]
    bs = min(EVAL_BATCH, n)
    return max(n // bs, 1) * bs


def taps(size: int, k: int) -> int:
    """Kernel taps that land inside the image, summed over the output
    positions of one axis of a SAME convolution: padding adds no work."""
    lo = (k - 1) // 2
    return sum(min(size - 1, i - lo + k - 1) - max(0, i - lo) + 1
               for i in range(size))


def layer_macs(w: dict) -> list:
    """Multiply-adds per example of each layer, input to output."""
    s, k = w["image_size"], w["kernel"]
    c0, (c1, c2) = w["channels"], w["conv_channels"]
    flat = (s // 4) * (s // 4) * c2
    return [
        taps(s, k) ** 2 * c0 * c1,  # conv1 at full resolution
        taps(s // 2, k) ** 2 * c1 * c2,  # conv2 after one pool
        flat * w["fc_width"],
        w["fc_width"] * w["num_classes"],
    ]


def forward_flops(config: dict) -> int:
    return 2 * sum(layer_macs(config["widths"]))


def train_flops(config: dict) -> int:
    """Forward, weight gradients (as much again) and input gradients of
    every layer but the first, whose input is the image."""
    macs = layer_macs(config["widths"])
    return 2 * (2 * sum(macs) + sum(macs[1:]))
