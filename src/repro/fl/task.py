"""FL task abstraction: anything with client-sharded data + a loss.

Two constructors: the paper's CNN classification task, and a causal-LM
task so any assigned architecture (reduced variant on CPU, full under the
production mesh) can be the federated workload.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.configs.paper_cnn import CNNConfig
from repro.data import partition_dirichlet, partition_iid
from repro.data.synthetic import ImageDataset, make_token_stream
from repro.models import cnn as cnn_mod
from repro.models import factory


@dataclasses.dataclass(frozen=True)
class FLTask:
    name: str
    init: Callable  # key -> params
    loss_fn: Callable  # (params, batch) -> scalar
    eval_fn: Callable  # (params) -> dict (accuracy/loss on held-out data)
    client_data: Dict  # pytree, leading axis = n_clients
    examples_per_client: int
    # optional batched-eval seam for cohort-parallel engines: the same
    # metrics as ``eval_fn`` but computed from explicitly-passed held-out
    # data (``eval_batch_fn(params, eval_data)``), so the engine can lay
    # the eval-batch axis out over a device mesh while params stay
    # replicated. ``eval_data``'s leading axis is the *usable* eval
    # prefix ``eval_fn`` scores (it drops the last partial batch), so the
    # two paths agree up to floating-point reduction order. Tasks without
    # these fields fall back to the replicated ``eval_fn`` everywhere.
    eval_data: Optional[Dict] = None  # pytree, leading axis = eval examples
    eval_batch_fn: Optional[Callable] = None  # (params, eval_data) -> dict


# ---------------------------------------------------------------------------
# Paper CNN task
# ---------------------------------------------------------------------------


def make_cnn_task(
    cfg: CNNConfig,
    train: ImageDataset,
    test: ImageDataset,
    n_clients: int,
    noniid_alpha: Optional[float] = None,
    seed: int = 0,
) -> FLTask:
    if noniid_alpha is None:
        parts = partition_iid(len(train.labels), n_clients, seed)
    else:
        parts = partition_dirichlet(train.labels, n_clients, alpha=noniid_alpha, seed=seed)
    cx = jnp.asarray(train.images[parts])  # (n, shard, H, W, C)
    cy = jnp.asarray(train.labels[parts])  # (n, shard)
    tx, ty = jnp.asarray(test.images), jnp.asarray(test.labels)

    def loss_fn(params, batch):
        logits = cnn_mod.forward(params, batch["x"])
        logp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(logp, batch["y"][:, None], axis=-1).mean()

    @jax.jit
    def eval_scan(params, tx, ty):
        # batched eval to bound memory
        bs = min(500, int(tx.shape[0]))
        nb = max(tx.shape[0] // bs, 1)

        def body(carry, i):
            correct, loss = carry
            xb = jax.lax.dynamic_slice_in_dim(tx, i * bs, bs)
            yb = jax.lax.dynamic_slice_in_dim(ty, i * bs, bs)
            logits = cnn_mod.forward(params, xb)
            logp = jax.nn.log_softmax(logits)
            loss += -jnp.take_along_axis(logp, yb[:, None], axis=-1).sum()
            correct += (logits.argmax(-1) == yb).sum()
            return (correct, loss), None

        (correct, loss), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.int32), jnp.zeros(())), jnp.arange(nb)
        )
        ntot = nb * bs
        return {"accuracy": correct / ntot, "loss": loss / ntot}

    n_used = max(tx.shape[0] // min(500, int(tx.shape[0])), 1) * min(
        500, int(tx.shape[0])
    )

    def eval_batch_fn(params, data):
        # one full-width pass: under a mesh the batch axis is sharded, so
        # each device scores 1/devices of the prefix and the sums reduce
        logits = cnn_mod.forward(params, data["x"])
        logp = jax.nn.log_softmax(logits)
        n = data["y"].shape[0]
        loss = -jnp.take_along_axis(logp, data["y"][:, None], axis=-1).sum() / n
        correct = (logits.argmax(-1) == data["y"]).sum()
        return {"accuracy": correct / n, "loss": loss}

    return FLTask(
        name=cfg.name,
        init=lambda key: cnn_mod.init_params(key, cfg),
        loss_fn=loss_fn,
        # the test set is an argument, not a constant of the program
        eval_fn=functools.partial(eval_scan, tx=tx, ty=ty),
        client_data={"x": cx, "y": cy},
        examples_per_client=int(cx.shape[1]),
        eval_data={"x": tx[:n_used], "y": ty[:n_used]},
        eval_batch_fn=eval_batch_fn,
    )


# ---------------------------------------------------------------------------
# Causal-LM task (any assigned architecture as the FL workload)
# ---------------------------------------------------------------------------


def make_lm_task(
    cfg: ArchConfig,
    n_clients: int,
    seq_len: int = 128,
    docs_per_client: int = 16,
    seed: int = 0,
) -> FLTask:
    model = factory.build(cfg)
    total = n_clients * docs_per_client * (seq_len + 1)
    stream = make_token_stream(cfg.vocab_size, total + seq_len, seed)
    docs = np.lib.stride_tricks.sliding_window_view(stream, seq_len + 1)[
        : n_clients * docs_per_client * (seq_len + 1) : seq_len + 1
    ][: n_clients * docs_per_client]
    docs = docs.reshape(n_clients, docs_per_client, seq_len + 1)
    cdata = {"docs": jnp.asarray(docs)}
    held = jnp.asarray(
        np.lib.stride_tricks.sliding_window_view(
            make_token_stream(cfg.vocab_size, 32 * (seq_len + 1) + seq_len, seed + 99),
            seq_len + 1,
        )[:: seq_len + 1][:32]
    )

    def loss_fn(params, batch):
        docs_b = batch["docs"]  # (bs, seq+1)
        b = {"tokens": docs_b[:, :-1], "labels": docs_b[:, 1:]}
        loss, _ = model.loss(params, b)
        return loss

    @jax.jit
    def eval_fn(params):
        loss = loss_fn(params, {"docs": held})
        return {"loss": loss, "accuracy": -loss}  # higher is better convention

    def eval_batch_fn(params, data):
        loss = loss_fn(params, data)
        return {"loss": loss, "accuracy": -loss}

    return FLTask(
        name=f"lm:{cfg.name}",
        init=model.init,
        loss_fn=loss_fn,
        eval_fn=eval_fn,
        client_data=cdata,
        examples_per_client=docs_per_client,
        eval_data={"docs": held},
        eval_batch_fn=eval_batch_fn,
    )
