"""GPipe-style pipeline parallelism over a mesh axis.

For multi-pod topologies the ``pod`` axis can run as a *pipeline* axis
instead of outer data parallelism: layers are split into S stages, each
stage lives on one slice of the axis, and micro-batches stream through
with ``ppermute`` hops between stages.  Implemented with ``shard_map``
so stage code is explicit (no GSPMD guessing), using the classic
rotating-buffer schedule: at step k, stage s processes micro-batch
(k - s); bubble = (S - 1) / (S - 1 + M).

This is the building block for "PP across pods, TP+FSDP within a pod";
tested for exact equivalence with the single-device forward in
tests/test_pipeline.py.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_forward(
    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    stage_params: Any,  # leaves with leading [S] stage axis
    x_micro: jnp.ndarray,  # [M, micro_batch, ...] micro-batches
    mesh: Mesh,
    axis: str = "stage",
):
    """Run M micro-batches through S = mesh.shape[axis] stages.

    ``stage_fn(params_s, x)`` applies one stage.  Returns [M, ...]
    outputs (as produced by the last stage).
    """
    s = mesh.shape[axis]
    m = x_micro.shape[0]
    steps = m + s - 1

    def local(params_local, xs_local):
        # params_local: stage-s params ([1, ...] leaves); xs_local: all
        # micro-batches, only stage 0 consumes them.
        params_s = jax.tree.map(lambda a: a[0], params_local)
        sid = jax.lax.axis_index(axis)
        buf = jnp.zeros_like(xs_local[0])  # current activation
        outs = jnp.zeros((steps,) + xs_local.shape[1:], xs_local.dtype)

        def step(carry, k):
            buf, outs = carry
            # stage 0 ingests micro-batch k (if in range), others take
            # the value passed from the previous stage
            feed = jnp.where(
                sid == 0,
                xs_local[jnp.clip(k, 0, m - 1)],
                buf,
            )
            y = stage_fn(params_s, feed)
            # pass activations down the pipe: stage i -> i+1
            nxt = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % s) for i in range(s)]
            )
            outs = outs.at[k].set(y)  # last stage's y is the result
            return (nxt, outs), None

        (buf, outs), _ = jax.lax.scan(step, (buf, outs), jnp.arange(steps))
        return outs[None]  # [1, steps, ...] stage-local

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis), P(None)),
        out_specs=P(axis),
        check_vma=False,
    )
    outs = fn(stage_params, x_micro)  # [S, steps, ...]
    # micro-batch j exits the last stage at step j + (S - 1)
    return outs[s - 1, s - 1 : s - 1 + m]
