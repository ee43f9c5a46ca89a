"""A second model module, for the tests alone: a two-layer MLP (McMahan
et al.'s 2NN, one hidden ReLU layer) over the paper CNN's images.

It brings everything a configuration's model module brings
(``bench/models/__init__.py``): its data (the CNN's), its program task
(a ``repro.fl.task.FLTask`` built here), its reference (``init``,
``loss``, ``eval_loss``) and its FLOP counts. A test puts it in ``bench.models``'s
place under the name ``toy_mlp`` and runs the harness unedited.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.models import paper_cnn

make_data = paper_cnn.make_data
eval_examples = paper_cnn.eval_examples


def _sizes(config: dict):
    w = config["widths"]
    return (w["image_size"] ** 2 * w["channels"], w["hidden"],
            w["num_classes"])


def init(key, config: dict, dtype):
    d, h, c = _sizes(config)
    k1, k2 = jax.random.split(key)
    p = {"fc1": {"w": jax.random.normal(k1, (d, h)) * (2.0 / d) ** 0.5,
                 "b": jnp.zeros((h,))},
         "fc2": {"w": jax.random.normal(k2, (h, c)) * (2.0 / h) ** 0.5,
                 "b": jnp.zeros((c,))}}
    return jax.tree.map(lambda a: a.astype(dtype), p)


def _logits(p, x):
    x = x.reshape(x.shape[0], -1)
    h = jax.nn.relu(jnp.dot(x, p["fc1"]["w"]) + p["fc1"]["b"])
    return jnp.dot(h, p["fc2"]["w"]) + p["fc2"]["b"]


def _xent(logits, y):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    return -jnp.take_along_axis(logp, y[:, None], axis=-1).mean()


def loss(p, x, y):
    return _xent(_logits(p, x), y)


def eval_loss(p, test):
    tx, ty = test
    n = max(tx.shape[0] // min(paper_cnn.EVAL_BATCH, tx.shape[0]), 1) * min(
        paper_cnn.EVAL_BATCH, tx.shape[0])
    return loss(p, tx[:n], ty[:n])


def build_task(config: dict, data):
    from repro.fl.task import FLTask

    n = eval_examples(data)

    @jax.jit
    def eval_fn(params, tx, ty):
        logits = _logits(params, tx)
        return {"loss": _xent(logits, ty),
                "accuracy": (logits.argmax(-1) == ty).mean()}

    return FLTask(
        name=config["name"],
        init=lambda key: init(key, config, jnp.float32),
        loss_fn=lambda params, batch: _xent(_logits(params, batch["x"]),
                                            batch["y"]),
        eval_fn=functools.partial(eval_fn, tx=data.test_x[:n],
                                  ty=data.test_y[:n]),
        client_data={"x": data.x, "y": data.y},
        examples_per_client=int(data.x.shape[1]))


def forward_flops(config: dict) -> int:
    d, h, c = _sizes(config)
    return 2 * (d * h + h * c)


def train_flops(config: dict) -> int:
    d, h, c = _sizes(config)
    return 2 * (2 * (d * h + h * c) + h * c)
