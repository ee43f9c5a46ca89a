"""The paper's CNN (McMahan et al., arXiv 1602.05629): two 5x5 SAME
convolutions (32, 64 channels), each followed by ReLU and a 2x2 max-pool,
a 512-wide ReLU layer and the class logits.

``build_task`` hands the program its own CNN task with the benchmark's
on-device data in place of the host-built client data. The FLOP counts
are the work the algorithm needs for one example, counted from the
widths: multiply-adds of the convolutions (only the taps inside the
image) and of the dense layers, two FLOPs each; bias, ReLU, pooling and
softmax are left out (under 1% here).
"""
from __future__ import annotations

import dataclasses


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


def forward_flops(w: dict) -> int:
    return 2 * sum(layer_macs(w))


def train_flops(w: dict) -> int:
    """Forward, weight gradients (as much again) and input gradients of
    every layer but the first, whose input is the image."""
    macs = layer_macs(w)
    return 2 * (2 * sum(macs) + sum(macs[1:]))
