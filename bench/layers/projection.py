"""One private linear projection ``Y = X W``: the layer of a configuration
that names none (see ``bench/layers/__init__.py`` for the contract).

The configuration states the deployment: the private weight's shape
(``hidden_size`` x ``intermediate_size`` for an up-projection, the
reverse for a down-projection), the coded-computing scheme, the worker
pool and its latency model, and ``max_batch``.  The cell's chip count
says whether Phase 2 runs across a mesh.  Everything random comes from
``--seed``: the weight, made on the device in one jitted call; the
activations (``bench.workload.Activations``), one stream in submission
order; and the pool's per-replay traces.

The weight and the activations are uniform in [-1, 1).  The engine picks
one power-of-two fixed-point scale per request so that the product
cannot wrap mod p; at these contraction depths that scale is 2, so
operands round to integers in [-2, 2].  Gaussian weights of standard
deviation 1/sqrt(k) would all round to 0 there, and every served ``Y``
would be 0.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, List, Sequence

import numpy as np

from bench import counts, reference as plain
from bench.workload import _ENGINE, _TRACES, _W, _seed32


@dataclass(frozen=True)
class Deployment:
    """The parts of a configuration file the harness acts on."""

    name: str
    k: int
    out: int
    method: str
    s: int
    t: int
    z: int
    n_spare: int
    p: int
    max_batch: int
    latency_shift: float
    latency_scale: float
    net_scale: float
    n_traces: int

    @property
    def in_width(self) -> int:
        return self.k

    @classmethod
    def from_dict(cls, d: dict) -> "Deployment":
        proj = d["projection"]
        if proj == "up":
            k, out = d["hidden_size"], d["intermediate_size"]
        elif proj == "down":
            k, out = d["intermediate_size"], d["hidden_size"]
        else:
            raise ValueError(f"unknown projection {proj!r}")
        sch, pool = d["scheme"], d["pool"]
        return cls(
            name=d["name"], k=int(k), out=int(out), method=sch["method"],
            s=int(sch["s"]), t=int(sch["t"]), z=int(sch["z"]),
            n_spare=int(pool["spares"]), p=int(d["field_p"]),
            max_batch=int(d["max_batch"]),
            latency_shift=float(pool["compute_latency"]["shift"]),
            latency_scale=float(pool["compute_latency"]["scale"]),
            net_scale=float(pool["net_scale"]), n_traces=int(pool["traces"]),
        )


from_dict = Deployment.from_dict


def make_weights(dep: Deployment, seed: int) -> np.ndarray:
    """``W [k, out]`` made on the device in one call, as the float64 host
    array the engine serves from."""
    import jax

    key = jax.random.PRNGKey(_seed32(seed, _W))
    gen = jax.jit(lambda k: jax.random.uniform(k, (dep.k, dep.out), minval=-1.0, maxval=1.0))
    return np.asarray(gen(key), np.float64)


def make_engine(dep: Deployment, w: np.ndarray, seed: int, devices: List[Any]):
    """The system under test: a ``ServingEngine`` over the seeded pool,
    with Phase 2 across a ``workers`` mesh of ``devices`` when there are
    more than one.

    The simulated clock neither sheds nor defers (no SLO, admission off),
    so the host clock alone times a request, and the engine's ``validate``
    oracle stays off: it is not part of the served path.
    """
    from repro.core.constructions import PlanConfig
    from repro.core.gf import Field
    from repro.runtime.pool import ShiftedExponential, sample_trace
    from repro.serve import ServingEngine

    cfg = PlanConfig(dep.method, dep.s, dep.t, dep.z)
    pool = cfg.n_workers + dep.n_spare
    latency = ShiftedExponential(dep.latency_shift, dep.latency_scale)
    traces = [
        sample_trace(pool, latency, seed=_seed32(seed, _TRACES, i),
                     net_scale=dep.net_scale)
        for i in range(dep.n_traces)
    ]
    mesh = None
    if len(devices) > 1:
        from jax.sharding import Mesh

        mesh = Mesh(np.array(devices), ("workers",))
    return ServingEngine(
        w, traces, cfg, field=Field(dep.p), max_batch=dep.max_batch, admission=False,
        validate=False, seed=_seed32(seed, _ENGINE), mesh=mesh,
    )


def reference(dep: Deployment, w: np.ndarray, xs: Sequence[np.ndarray]) -> List[np.ndarray]:
    return plain.reference(xs, w, dep.p)


def control(dep: Deployment, w: np.ndarray, xs: Sequence[np.ndarray]) -> List[np.ndarray]:
    return plain.control(xs, w, dep.p)


def worker_products(dep: Deployment, rows: int) -> List[tuple]:
    return [counts.worker_product(dep.k, dep.out, rows, dep.s, dep.t)]


def request_flops(dep: Deployment, rows: int) -> int:
    return counts.request_flops(rows, dep.k, dep.out)
