"""One chip's share of DeepSeek-V3's routed-expert (MoE) layer, served
privately (see ``bench/layers/__init__.py`` for the contract).

The configuration states the deployment: expert parallelism over the
chips that share one MoE layer, of which this chip holds the experts
``deployment.held_experts``.  The router keeps its published width (the
``published`` count of routed experts) and its top-k; the chip computes
the shared expert and its own experts' part of the routed output, and
what the absent experts would add is left out, in the program and in
the reference alike.  The engine is ``repro.serve.moe.PrivateMoE``:
router, gate/up and down products through one ``ServingEngine`` on one
worker pool, the routing, SwiGLU and combine at the client.

Data, from ``--seed`` (the configuration's ``data`` says why): the
router has ``router_nonzeros`` entries of +-1 per column and 0
elsewhere, so its fixed-point logits are multiples of 1/2 that neither
vanish nor saturate the sigmoid; the routing bias is uniform in
``[-bias_amplitude, bias_amplitude)``; every expert matrix is uniform
in [-1, 1), as in the projection's configurations.

``reference`` is the layer in plain float64 numpy, importing nothing of
the program: the engine's scale rule written out, the exact fixed-point
product of each private product, and the client's steps in the same
operations and order as ``repro.serve.moe`` documents them.  Requests
form steps as the program forms them (up to ``max_batch`` in
submission order, no more rows than the top row bucket), since a
product's scale is that of its step's rows.  ``control`` puts the
float32 field product in the program's place at every private product.
"""
from __future__ import annotations

import dataclasses
import importlib.util
from typing import Any, Dict, List, Sequence

import numpy as np

from bench import counts, reference as plain
from bench.workload import _ENGINE, _TRACES, _W, _seed32

PARTS = ("gate", "up", "down")


@dataclasses.dataclass(frozen=True)
class Deployment:
    """The parts of a configuration file the harness and the layer act on."""

    name: str
    hidden: int
    width: int  # moe_intermediate_size
    n_experts: int  # routed experts the router scores (published)
    top_k: int
    n_group: int
    topk_group: int
    scaling: float
    held: tuple
    method: str
    s: int
    t: int
    z: int
    n_spare: int
    p: int
    max_batch: int
    row_buckets: tuple
    latency_shift: float
    latency_scale: float
    net_scale: float
    n_traces: int
    router_nonzeros: int
    bias_amplitude: float

    @property
    def in_width(self) -> int:
        return self.hidden


def from_dict(d: dict) -> Deployment:
    if importlib.util.find_spec("repro.serve.moe") is None:
        raise RuntimeError("this program has no repro.serve.moe: it cannot serve this layer")
    want = {"scoring_func": "sigmoid", "topk_method": "noaux_tc", "norm_topk_prob": True,
            "hidden_act": "silu", "n_shared_experts": 1}
    for key, value in want.items():
        if d[key] != value:
            raise ValueError(f"{key}={d[key]!r}: the layer implements {value!r}")
    dep, sch, pool, data = d["deployment"], d["scheme"], d["pool"], d["data"]
    held = tuple(int(e) for e in dep["held_experts"])
    if len(held) != d["n_routed_experts"]:
        raise ValueError(f"n_routed_experts={d['n_routed_experts']} but {len(held)} held")
    return Deployment(
        name=d["name"], hidden=int(d["hidden_size"]), width=int(d["moe_intermediate_size"]),
        n_experts=int(d["published"]["n_routed_experts"]), top_k=int(d["num_experts_per_tok"]),
        n_group=int(d["n_group"]), topk_group=int(d["topk_group"]),
        scaling=float(d["routed_scaling_factor"]), held=held, method=sch["method"],
        s=int(sch["s"]), t=int(sch["t"]), z=int(sch["z"]), n_spare=int(pool["spares"]),
        p=int(d["field_p"]), max_batch=int(d["max_batch"]),
        row_buckets=tuple(int(b) for b in d["engine"]["row_buckets"]),
        latency_shift=float(pool["compute_latency"]["shift"]),
        latency_scale=float(pool["compute_latency"]["scale"]),
        net_scale=float(pool["net_scale"]), n_traces=int(pool["traces"]),
        router_nonzeros=int(data["router_nonzeros"]),
        bias_amplitude=float(data["bias_amplitude"]),
    )


def _name(expert, part: str) -> str:
    return f"shared.{part}" if expert is None else f"expert.{expert}.{part}"


def make_weights(dep: Deployment, seed: int) -> Dict[str, np.ndarray]:
    """The router, its bias, and the shared and held experts' matrices as
    float64 host arrays; the expert matrices are made on the device."""
    import jax

    rng = np.random.default_rng(np.random.SeedSequence([seed, _W]))
    router = np.zeros((dep.hidden, dep.n_experts))
    for e in range(dep.n_experts):
        rows = rng.choice(dep.hidden, dep.router_nonzeros, replace=False)
        router[rows, e] = rng.choice([-1.0, 1.0], dep.router_nonzeros)
    weights = {
        "router": router,
        "bias": rng.uniform(-dep.bias_amplitude, dep.bias_amplitude, dep.n_experts),
    }
    shapes = {"gate": (dep.hidden, dep.width), "up": (dep.hidden, dep.width),
              "down": (dep.width, dep.hidden)}
    gen = {shape: jax.jit(lambda k, shape=shape: jax.random.uniform(
        k, shape, minval=-1.0, maxval=1.0)) for shape in set(shapes.values())}
    for expert in (None,) + dep.held:  # an expert's stream is its index's
        for j, part in enumerate(PARTS):
            key = jax.random.PRNGKey(_seed32(seed, _W, 1 if expert is None else 2 + expert, j))
            weights[_name(expert, part)] = np.asarray(gen[shapes[part]](key), np.float64)
    return weights


def make_engine(dep: Deployment, weights: Dict[str, np.ndarray], seed: int, devices: List[Any]):
    """The system under test: a ``PrivateMoE`` over the seeded pool, every
    program it can launch compiled here (``warm``), before any window."""
    from repro.core.constructions import PlanConfig
    from repro.core.gf import Field
    from repro.runtime.pool import ShiftedExponential, sample_trace
    from repro.serve.moe import MoEConfig, PrivateMoE

    cfg = PlanConfig(dep.method, dep.s, dep.t, dep.z)
    pool = cfg.n_workers + dep.n_spare
    latency = ShiftedExponential(dep.latency_shift, dep.latency_scale)
    traces = [
        sample_trace(pool, latency, seed=_seed32(seed, _TRACES, i), net_scale=dep.net_scale)
        for i in range(dep.n_traces)
    ]
    mesh = None
    if len(devices) > 1:
        from jax.sharding import Mesh

        mesh = Mesh(np.array(devices), ("workers",))
    moe = PrivateMoE(
        weights, dep.held,
        MoEConfig(dep.n_experts, dep.top_k, dep.n_group, dep.topk_group, dep.scaling),
        traces, cfg, bias=weights["bias"], row_buckets=dep.row_buckets,
        max_batch=dep.max_batch, field=Field(dep.p),
        admission=False, validate=False, seed=_seed32(seed, _ENGINE), mesh=mesh,
    )
    moe.warm(seed=_seed32(seed, _ENGINE, 1))
    return moe


# -- the plain layer ----------------------------------------------------


def _steps(dep: Deployment, xs: Sequence[np.ndarray]) -> List[List[int]]:
    """Request indices of each step, formed as the program forms them."""
    steps, cur, rows = [], [], 0
    for i, x in enumerate(xs):
        if len(cur) == dep.max_batch or rows + len(x) > dep.row_buckets[-1]:
            steps.append(cur)
            cur, rows = [], 0
        cur.append(i)
        rows += len(x)
    return steps + ([cur] if cur else [])


def _layer(dep: Deployment, weights, xs, product) -> List[np.ndarray]:
    quantized: Dict[tuple, np.ndarray] = {}

    def private(name: str, x: np.ndarray) -> np.ndarray:
        w = weights[name]
        s = plain.scale_for(w.shape[0], float(np.abs(x).max() + 1e-9),
                            float(np.abs(w).max() + 1e-9), dep.p)
        if (name, s) not in quantized:
            quantized[name, s] = np.rint(w * s)
        return product(np.rint(x * s), quantized[name, s], dep.p) / float(s * s)

    out: List[np.ndarray] = [None] * len(xs)
    for step in _steps(dep, xs):
        x = np.concatenate([xs[i] for i in step])
        y = _step(dep, weights, x, private)
        at = 0
        for i in step:
            out[i] = y[at:at + len(xs[i])]
            at += len(xs[i])
    return out


def _step(dep: Deployment, weights, x: np.ndarray, private) -> np.ndarray:
    n = len(x)
    # routing: sigmoid scores, bias, best groups by their top-2 sum, top-k
    scores = 1.0 / (1.0 + np.exp(-private("router", x)))
    c = scores + weights["bias"]
    ranked = np.sort(c.reshape(n, dep.n_group, -1), axis=-1)
    group_score = ranked[..., -1] + ranked[..., -2]
    best = np.argsort(-group_score, axis=-1, kind="stable")[:, : dep.topk_group]
    in_best = np.zeros((n, dep.n_group), bool)
    in_best[np.arange(n)[:, None], best] = True
    per_group = dep.n_experts // dep.n_group
    masked = np.where(np.repeat(in_best, per_group, axis=1), c, -np.inf)
    top = np.argsort(-masked, axis=-1, kind="stable")[:, : dep.top_k]
    chosen = scores[np.arange(n)[:, None], top]
    total = chosen[:, 0]
    for i in range(1, dep.top_k):
        total = total + chosen[:, i]
    gate_w = chosen / (total[:, None] + 1e-20) * dep.scaling

    def expert(e, xe):
        g, u = private(_name(e, "gate"), xe), private(_name(e, "up"), xe)
        with np.errstate(over="ignore"):
            h = g / (1.0 + np.exp(-g)) * u
        j = np.frexp(np.abs(h).max(axis=1))[1] + 1
        return np.ldexp(private(_name(e, "down"), np.ldexp(h, -j[:, None])), j[:, None])

    y = expert(None, x)
    for e in dep.held:
        rows, slot = np.nonzero(top == e)
        if len(rows):
            y[rows] += gate_w[rows, slot][:, None] * expert(e, x[rows])
    return y


def reference(dep: Deployment, weights, xs: Sequence[np.ndarray]) -> List[np.ndarray]:
    """Every private product exact: float64 over the rounded operands."""
    return _layer(dep, weights, xs, plain._exact_product)


def control(dep: Deployment, weights, xs: Sequence[np.ndarray]) -> List[np.ndarray]:
    """Every private product in float32 over the field representatives."""
    return _layer(dep, weights, xs, plain._float32_field_product)


def worker_products(dep: Deployment, rows: int) -> List[tuple]:
    """Each stage's worker product at every row bucket, largest first
    (``counts.unpad`` takes the first product a launched call holds)."""
    shapes = [(dep.hidden, dep.n_experts), (dep.hidden, dep.width), (dep.width, dep.hidden)]
    return sorted(
        (counts.worker_product(k, out, b, dep.s, dep.t)
         for b in dep.row_buckets for k, out in shapes),
        reverse=True,
    )


def request_flops(dep: Deployment, rows: int) -> int:
    """Router and shared expert for every row, and the routed experts held
    here at their expected share: ``top_k`` of ``n_experts`` picks per
    token land on each held expert (0.25 a token for 8 of 256, top 8);
    an expectation, not a count of the window's routing."""
    expert = 3 * 2 * dep.hidden * dep.width
    routed = rows * expert * dep.top_k * len(dep.held) // dep.n_experts
    return rows * (2 * dep.hidden * dep.n_experts + expert) + routed
