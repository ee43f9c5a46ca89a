"""Model modules: everything the harness knows of a model.

A configuration's ``model`` key names its module, ``bench/models/<model>.py``,
and nothing outside that module knows the model, its data or its sizes. So
a new configuration adds its JSON file and, for a new model, one module,
and edits no file of the harness. Each function that takes ``config`` is
given the whole configuration file.

- ``make_data(key, config, shards)``: the cell's data, made on the device
  from ``key``. It has ``x`` and ``y``, every client's examples and
  targets, arrays whose two leading axes are (clients, examples per
  client), and ``test``, the pytree ``eval_loss`` reads. With ``shards`` >
  1 the client axis is laid out over the fleet's mesh.
- ``build_task(config, data)``: the program's ``FLTask`` over that data.
  It imports the program; nothing else in the module does.
- The plain reference's model, in float32 ``jax.numpy`` with its matrix
  products at the precision the configuration states for the program's
  (JAX's default in both; the bfloat16 control calls the same functions
  with bfloat16 parameters and data):
  ``init(key, config, dtype)``, the parameters;
  ``loss(params, x, y)``, the training loss of one batch of examples;
  ``eval_loss(params, test)``, the mean loss of one eval.
- The FLOPs the algorithm needs for one example, ``train_flops(config)``
  (forward and backward) and ``forward_flops(config)``, and
  ``eval_examples(data)``, the test examples one eval runs forward.
"""
from __future__ import annotations

import importlib


def load(config: dict):
    """The module of ``config["model"]``."""
    return importlib.import_module(f"{__name__}.{config['model']}")
