"""Chunked, donated scan execution of an engine's step function.

``ChunkRunner`` turns a pure per-step function into a family of jitted
``lax.scan`` drivers that advance ``length`` steps per host dispatch:

  * the whole engine state (params, model ring buffer, event/sched state,
    accumulators, run key) is the scan carry and is **donated** to the
    compiled chunk, so XLA updates buffers in place instead of copying
    the fleet state every step;
  * the per-step key schedule stays ``fold_in(k_run, r)`` with the global
    step index threaded through the scan — a chunk is a pure function of
    ``(state, r0)``, so chunked execution is bit-for-bit identical to
    per-step execution (pinned by ``tests/test_engine_chunked.py``);
  * the device-resident selection accumulators
    (``core.load_metric.init/update_selection_accum``) are folded inside
    the scan body, killing the per-step device->host sync of the ``(n,)``
    selection vector that used to dominate fleet-scale runs;
  * per-step aux outputs are stacked on device and handed back as one
    pytree — the caller performs a single host transfer per chunk.

Compiled drivers are cached per ``(length, with_history)``; together with
``repro.engine.config.chunk_plan`` (at most three distinct chunk lengths
per run) this bounds recompilation to a handful of variants.
"""
from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.load_metric import update_selection_accum

# state keys the runner owns; the engine step function never sees them
_RUNNER_KEYS = ("k_run", "load_acc")


class ChunkRunner:
    """Compile-once-per-shape chunked driver over ``step(state, key, data)``.

    ``step_fn`` is the engine's pure per-step function: it takes the
    engine's jittable state (without the runner-owned ``k_run`` /
    ``load_acc`` entries), a folded key and ``data``, and returns
    ``(state, aux)`` where ``aux`` contains at least ``send`` (the (n,)
    bool selection vector) plus any per-step scalars. ``aux_keys`` names
    the aux entries stacked and returned per step; ``send`` is
    additionally stacked when the caller asks for history.

    ``data`` is the read-only input every step reads (the task's client
    data). It enters each compiled chunk as an argument, never donated:
    closed over, XLA would embed it as a constant, and at fleet scale
    (gigabytes of client examples) that costs minutes of compile and a
    program too large for the persistent compilation cache.
    """

    def __init__(self, step_fn: Callable, aux_keys: Tuple[str, ...],
                 data=None):
        self._step_fn = step_fn
        self._aux_keys = aux_keys
        self._data = data
        self._compiled: Dict[Tuple[int, bool], Callable] = {}

    def _build(self, length: int, with_history: bool) -> Callable:
        step_fn, aux_keys = self._step_fn, self._aux_keys

        def chunk(state, data, r0):
            def body(carry, r):
                key = jax.random.fold_in(carry["k_run"], r)
                inner = {k: v for k, v in carry.items() if k not in _RUNNER_KEYS}
                inner, aux = step_fn(inner, key, data)
                with jax.named_scope("load_metric"):
                    load_acc = update_selection_accum(carry["load_acc"],
                                                      aux["send"])
                carry = {**inner, "k_run": carry["k_run"], "load_acc": load_acc}
                ys = {k: aux[k] for k in aux_keys}
                if with_history:
                    ys["send"] = aux["send"]
                return carry, ys

            return jax.lax.scan(body, state, r0 + jnp.arange(length))

        return jax.jit(chunk, donate_argnums=0)

    def _fn(self, length: int, with_history: bool) -> Callable:
        key = (length, with_history)
        fn = self._compiled.get(key)
        if fn is None:
            fn = self._compiled[key] = self._build(length, with_history)
        return fn

    def __call__(self, state: Dict, r0: int, length: int, with_history: bool):
        """Advance ``length`` steps from global step ``r0``.

        Donates ``state``; returns ``(state', stacked_aux)`` with every
        ``stacked_aux`` leaf carrying a leading ``length`` axis, still on
        device (the caller decides when to transfer).
        """
        return self._fn(length, with_history)(
            state, self._data, jnp.asarray(r0, jnp.int32))

    def lower(self, state: Dict, r0: int, length: int, with_history: bool):
        """The chunk that ``__call__`` would run, lowered and not run
        (``jax.stages.Lowered``): its HLO shows which kernels it calls."""
        return self._fn(length, with_history).lower(
            state, self._data, jnp.asarray(r0, jnp.int32))


def step_once(runner: ChunkRunner, state: Dict, r: int):
    """One engine step, driven through the chunked runner as a length-1
    donated scan — the per-step path and the chunk path share a single
    implementation of the ``_RUNNER_KEYS`` bookkeeping (key folding and
    the device-resident selection accumulators), so the two can never
    drift. Donates ``state`` like any chunk; engine ``init()`` states are
    dealiased up front to keep that legal. Returns ``(state', aux)`` with
    the leading length-1 axis squeezed off every aux leaf (history is
    always kept at length 1, so ``aux`` includes ``send``)."""
    state, aux = runner(state, r, 1, with_history=True)
    return state, {k: v[0] for k, v in aux.items()}


def dealias_pytree(tree):
    """Donation-safe copy of duplicated leaves.

    jax's constant cache can hand the *same* device buffer to multiple
    identical leaves (the scalar zeros of a fresh accumulator, say), and
    XLA refuses to donate one buffer twice. Engine init states pass
    through this once before the first donated chunk; chunk outputs are
    already alias-free.
    """
    seen = set()

    def uniq(x):
        if id(x) in seen:
            return jnp.copy(x)
        seen.add(id(x))
        return x

    return jax.tree.map(uniq, tree)


def run_key(seed: int, rng_impl) -> jax.Array:
    """The run's root PRNG key: legacy ``PRNGKey`` (bit-compatible with
    pre-chunking runs) unless a counter-based impl is configured."""
    if rng_impl is None:
        return jax.random.PRNGKey(seed)
    return jax.random.key(seed, impl=rng_impl)
