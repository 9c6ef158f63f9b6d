"""Production mesh construction.

Single pod: 16 x 16 = 256 chips, axes (data, model).
Multi-pod:  2 x 16 x 16 = 512 chips, axes (pod, data, model) — the
``pod`` axis is outermost data parallelism over the inter-pod links.

``make_elastic_mesh`` builds the largest (data, model) grid over
whatever devices are currently alive — elastic scaling: checkpoints are
topology-agnostic (see checkpoint.manager) so a job can restart on a
shrunken fleet.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
from jax.sharding import AxisType


def _axis_kw(n):
    return {"axis_types": (AxisType.Auto,) * n}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_axis_kw(len(axes)))


def make_mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes), **_axis_kw(len(axes)))


def make_elastic_mesh(model_parallel: Optional[int] = None):
    """Largest (data, model) grid over the live device set."""
    n = len(jax.devices())
    if model_parallel is None:
        model_parallel = min(16, n)
        while n % model_parallel:
            model_parallel //= 2
    data = n // model_parallel
    return jax.make_mesh((data, model_parallel), ("data", "model"), **_axis_kw(2))


def describe(mesh) -> str:
    return f"mesh{dict(mesh.shape)} over {mesh.devices.size} devices"
