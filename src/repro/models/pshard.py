"""Activation-sharding context.

GSPMD propagates input/param shardings but, left unconstrained, may pick
pathological layouts (e.g. replicating the batch across the data axis
inside GQA attention when kv_heads < model-axis size — observed in the
dry-run profile). The launchers install a mesh context; model code calls
``constrain(x, axis0, axis1, ...)`` at layer boundaries. Every axis
request degrades gracefully: it is applied only if the mesh has the axis
and the dim divides, so the same model code runs unsharded in CPU tests.
"""
from __future__ import annotations

import contextlib
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_CTX = {"mesh": None}


@contextlib.contextmanager
def mesh_context(mesh: Optional[Mesh]):
    prev = _CTX["mesh"]
    _CTX["mesh"] = mesh
    try:
        yield
    finally:
        _CTX["mesh"] = prev


def current_mesh() -> Optional[Mesh]:
    return _CTX["mesh"]


def axis_size(name) -> int:
    mesh = _CTX["mesh"]
    if mesh is None:
        return 1
    if isinstance(name, tuple):
        out = 1
        for n in name:
            out *= axis_size(n)
        return out
    return mesh.shape.get(name, 1)


def dp() -> Tuple[str, ...]:
    mesh = _CTX["mesh"]
    if mesh is None:
        return ()
    return ("pod", "data") if "pod" in mesh.shape else ("data",)


def constrain(x, *axes):
    """with_sharding_constraint with per-axis divisibility fallback.

    ``axes`` entries: None | axis-name | tuple of axis names. Trailing dims
    may be omitted (replicated).
    """
    mesh = _CTX["mesh"]
    if mesh is None:
        return x
    spec = []
    used = set()
    for i, a in enumerate(x.shape[: len(axes)]):
        req = axes[i]
        if req is None:
            spec.append(None)
            continue
        names = req if isinstance(req, tuple) else (req,)
        names = tuple(n for n in names if n in mesh.shape)
        if not names or any(n in used for n in names):
            spec.append(None)
            continue
        size = 1
        for n in names:
            size *= mesh.shape[n]
        if a % size != 0:
            spec.append(None)
            continue
        spec.append(names if len(names) > 1 else names[0])
        used.update(names)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))
