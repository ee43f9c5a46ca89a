"""Pluggable server-side aggregation: pure ``init/accumulate/finalize``.

An ``Aggregator`` owns everything between "the cohort's local updates are
stacked on axis 0" and "here are the new global params", so the sync and
async engines share one aggregation seam instead of hardwiring their own:

    w     = agg.weigh(mask, staleness)        # (B,) float32 weights
    acc   = agg.init(global_params)           # accumulator pytree
    acc   = agg.accumulate(acc, updates, bases, w)
    new_g = agg.finalize(global_params, acc)

``updates`` is a pytree with a stacked cohort axis; ``bases`` is the
params each cohort member trained *from* (the dispatch-time ring-buffer
version in the async engine), which is what lets delta-based aggregators
express staleness correctly. ``bases`` may also be the *unstacked* global
tree — the sync engine passes the global params directly and the cohort
axis broadcasts lazily inside ``accumulate`` (``updates - bases``), so no
``(width, ...)`` copies are ever materialized. All functions are
jit-compatible and safe to call with an all-zero weight vector (an empty
buffer leaves the global params untouched).

Built-ins:
  * ``fedavg``  — weighted mean of the updated params (the paper's FedAvg
                  step (iii)); ignores staleness.
  * ``fedbuff`` — staleness-discounted mean of *deltas* added to the
                  global params (FedBuff/FedAsync style, ``(1+s)^-a``).
  * ``fedprox`` — fedbuff with server-side proximal damping: the mean
                  delta is scaled by ``1/(1+mu)``, i.e. the new params
                  minimize ``||p - (g + d)||^2 + mu * ||p - g||^2``.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from repro.engine.registry import register_aggregator


@dataclasses.dataclass(frozen=True)
class Aggregator:
    """The aggregation protocol both engines dispatch through.

    ``additive`` declares that the accumulator is a plain sum over cohort
    members: ``init`` is the zero element and accumulating two disjoint
    cohort slices then adding the accumulators leaf-wise equals
    accumulating the full cohort. It is what lets the cohort-sharded
    execution mode run ``accumulate`` shard-locally and merge with a
    single ``psum`` of the accumulator pytree
    (``cohort_sharded_apply``). The default is False — psum-merging an
    accumulator is only sound when the author has checked the property
    (a non-zero ``init`` or a max/median-style statistic would be
    silently wrong), so every aggregator opts in explicitly; all
    built-ins do.
    """

    name: str
    weigh: Callable  # (mask bool (B,), staleness i32 (B,)) -> f32 (B,)
    init: Callable  # (global_params) -> acc pytree
    accumulate: Callable  # (acc, updates, bases, weights) -> acc
    finalize: Callable  # (global_params, acc) -> new global_params
    additive: bool = False
    # scalar telemetry names the accumulator carries under acc["stats"]
    # (e.g. norm_clip's "clipped" count). Engines surface each as an
    # ``agg_<name>`` counter in RunResult.load_stats; () (every
    # non-robust built-in) adds no stats key and no per-step ops.
    stat_names: tuple = ()


def tree_where(cond, a, b):
    """Leaf-wise ``jnp.where`` under one scalar predicate — select a
    whole params/accumulator pytree without leaving jit (the defense
    tier's moving-target rule swap and empty-cohort guards use this)."""
    return jax.tree.map(lambda x, y: jnp.where(cond, x, y), a, b)


def acc_stats(acc) -> dict:
    """The scalar telemetry dict a finished accumulator carries (empty
    for aggregators that declare no ``stat_names``). Stats live *inside*
    the accumulator so they merge for free along every reduction path —
    psum under cohort sharding, segment-sum up a tier DAG."""
    return acc.get("stats", {}) if isinstance(acc, dict) else {}


def cohort_sharded_apply(
    agg: Aggregator, mesh, axis: str, stacked_bases: bool = True
) -> Callable:
    """The aggregator seam's shard-local path for cohort-parallel
    execution: ``apply(global_params, updates, bases, w) -> (new params,
    stats)`` with the cohort axis of ``updates``/``w`` (and ``bases``
    when stacked) laid out over ``axis`` of ``mesh``; ``stats`` is the
    merged accumulator's scalar telemetry (``acc_stats``).

    Each device runs ``agg.init``/``agg.accumulate`` over its own
    ``B/devices`` cohort slice, the accumulator pytrees are merged by one
    ``psum`` — O(params) cross-device traffic instead of shipping the
    ``B x params`` update stack through replication — and ``finalize``
    runs on the replicated merged accumulator. Requires ``agg.additive``
    and a cohort length divisible by the mesh (engines pad the cohort
    with zero-weight slots to the next multiple).

    ``stacked_bases=False`` is the sync engine's convention: ``bases`` is
    the *unstacked* global tree, replicated, broadcast lazily inside
    ``accumulate``.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    if not agg.additive:
        raise ValueError(
            f"aggregator {agg.name!r} is not additive: its accumulator "
            "cannot be merged by psum, so it cannot run cohort-sharded "
            "(drop shard_cohort for this aggregator)"
        )
    spec = P(axis)

    def apply(g, updates, bases, w, idx=None):
        # ``idx`` (the cohort -> client map) is part of the engines'
        # aggregate-hook signature for topology-aware reductions; the
        # star-shaped single-server reduction has no use for it
        def local(g_l, u_l, b_l, w_l):
            acc = agg.accumulate(agg.init(g_l), u_l, b_l, w_l)
            return jax.lax.psum(acc, axis)

        merged = shard_map(
            local,
            mesh=mesh,
            in_specs=(P(), spec, spec if stacked_bases else P(), spec),
            out_specs=P(),
        )(g, updates, bases, w)
        return agg.finalize(g, merged), acc_stats(merged)

    return apply


def staleness_weight(
    s: jnp.ndarray, mode: str = "poly", exp: float = 0.5
) -> jnp.ndarray:
    """Aggregation discount for an update of staleness ``s`` versions."""
    s = jnp.maximum(s.astype(jnp.float32), 0.0)
    if mode == "const":
        return jnp.ones_like(s)
    if mode == "poly":
        return (1.0 + s) ** (-exp)
    raise ValueError(f"unknown staleness mode {mode!r}")


def _wshape(u: jnp.ndarray) -> tuple:
    return (-1,) + (1,) * (u.ndim - 1)


@register_aggregator("fedavg")
def make_fedavg() -> Aggregator:
    """Weighted mean of updated params; empty cohorts keep the old params."""

    def weigh(mask, staleness):
        return mask.astype(jnp.float32)

    def init(g):
        return {
            "usum": jax.tree.map(lambda p: jnp.zeros(p.shape, p.dtype), g),
            "wsum": jnp.zeros((), jnp.float32),
        }

    def accumulate(acc, updates, bases, w):
        usum = jax.tree.map(
            lambda s, u: s + jnp.sum(u * w.reshape(_wshape(u)).astype(u.dtype), axis=0),
            acc["usum"], updates,
        )
        return {"usum": usum, "wsum": acc["wsum"] + w.sum()}

    def finalize(g, acc):
        empty = acc["wsum"] == 0.0
        denom = jnp.maximum(acc["wsum"], 1.0)

        def fin(gl, s):
            return jnp.where(empty, gl, (s / denom.astype(s.dtype)).astype(gl.dtype))

        return jax.tree.map(fin, g, acc["usum"])

    return Aggregator("fedavg", weigh, init, accumulate, finalize,
                      additive=True)


def _delta_aggregator(name: str, staleness_mode: str, staleness_exp: float,
                      scale: float) -> Aggregator:
    """Shared core of fedbuff/fedprox: staleness-weighted mean delta,
    scaled by ``scale`` and added to the global params."""

    def weigh(mask, staleness):
        return mask.astype(jnp.float32) * staleness_weight(
            staleness, staleness_mode, staleness_exp
        )

    def init(g):
        return {
            "dsum": jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), g),
            "wsum": jnp.zeros((), jnp.float32),
        }

    def accumulate(acc, updates, bases, w):
        dsum = jax.tree.map(
            lambda s, u, b: s
            + jnp.sum((u - b).astype(jnp.float32) * w.reshape(_wshape(u)), axis=0),
            acc["dsum"], updates, bases,
        )
        return {"dsum": dsum, "wsum": acc["wsum"] + w.sum()}

    def finalize(g, acc):
        has = acc["wsum"] > 0
        denom = jnp.maximum(acc["wsum"], 1e-9)

        def fin(gl, s):
            d = s / denom
            if scale != 1.0:
                d = d * scale
            upd = gl + d.astype(gl.dtype)
            return jnp.where(has, upd, gl)

        return jax.tree.map(fin, g, acc["dsum"])

    return Aggregator(name, weigh, init, accumulate, finalize,
                      additive=True)


@register_aggregator("fedbuff")
def make_fedbuff(staleness_mode: str = "poly", staleness_exp: float = 0.5) -> Aggregator:
    """Staleness-discounted buffered delta aggregation (FedBuff-style)."""
    return _delta_aggregator("fedbuff", staleness_mode, staleness_exp, scale=1.0)


@register_aggregator("fedprox")
def make_fedprox(prox_mu: float = 0.1, staleness_mode: str = "poly",
                 staleness_exp: float = 0.5) -> Aggregator:
    """Proximally damped delta aggregation: mean delta scaled by 1/(1+mu)."""
    if prox_mu < 0:
        raise ValueError(f"prox_mu must be >= 0, got {prox_mu}")
    return _delta_aggregator(
        "fedprox", staleness_mode, staleness_exp, scale=1.0 / (1.0 + prox_mu)
    )
