"""Dense MLP blocks: gated (SwiGLU) and ungated (GELU).

Two tensor-parallel execution paths:
  * GSPMD (default): einsums + sharding constraints; the partitioner
    inserts the row-parallel all-reduce. On the CPU pipeline
    float-normalization widens bf16 dot outputs to f32 *before* SPMD, so
    the AR moves 2x the bytes (§Perf finding).
  * explicit_tp: shard_map with a hand-written ``psum`` placed AFTER the
    cast to the activation dtype — collectives are guaranteed bf16, and
    the backward ``psum`` (cotangent of the replicated input) is bf16 too.
"""
from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.base import MLPSpec
from repro.models import pshard
from repro.models.common import activation, dense_init


def init_mlp(key, d_model: int, spec: MLPSpec, dtype) -> Dict:
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "w_in": dense_init(k1, (d_model, spec.d_ff), 0, dtype),
        "w_out": dense_init(k2, (spec.d_ff, d_model), 0, dtype),
    }
    if spec.activation == "silu":  # gated
        p["w_gate"] = dense_init(k3, (d_model, spec.d_ff), 0, dtype)
    return p


def mlp_fwd(p: Dict, x: jnp.ndarray, spec: MLPSpec, explicit_tp: bool = False) -> jnp.ndarray:
    mesh = pshard.current_mesh()
    if (
        explicit_tp
        and x.ndim == 3
        and mesh is not None
        and "model" in mesh.shape
        and spec.d_ff % mesh.shape["model"] == 0
        and "w_gate" in p
    ):
        return _mlp_fwd_explicit_tp(p, x, spec, mesh)
    act = activation(spec.activation)
    dpax = pshard.dp()
    h = jnp.einsum("...d,df->...f", x, p["w_in"])
    if x.ndim == 3:
        h = pshard.constrain(h, dpax, None, "model")
    if "w_gate" in p:
        g = jnp.einsum("...d,df->...f", x, p["w_gate"])
        if x.ndim == 3:
            g = pshard.constrain(g, dpax, None, "model")
        h = act(g) * h
    else:
        h = act(h)
    return jnp.einsum("...f,fd->...d", h, p["w_out"])


def _mlp_fwd_explicit_tp(p: Dict, x: jnp.ndarray, spec: MLPSpec, mesh) -> jnp.ndarray:
    """Column-parallel in/gate + row-parallel out with an explicit bf16
    psum over the model axis (Megatron TP with hand-placed collectives)."""
    act = activation(spec.activation)
    dp = pshard.dp() or None

    def local(x_l, win_l, wg_l, wo_l):
        h = jnp.einsum("bsd,df->bsf", x_l, win_l)
        g = jnp.einsum("bsd,df->bsf", x_l, wg_l)
        y = jnp.einsum("bsf,fd->bsd", act(g) * h, wo_l)
        # the cast happens BEFORE the collective: psum moves x.dtype bytes
        return jax.lax.psum(y.astype(x_l.dtype), "model")

    xspec = P(dp, None, None) if dp else P(None, None, None)
    return shard_map(
        local,
        mesh=mesh,
        in_specs=(xspec, P(None, "model"), P(None, "model"), P("model", None)),
        out_specs=xspec,
        check_vma=False,
    )(x, p["w_in"], p["w_gate"], p["w_out"])
