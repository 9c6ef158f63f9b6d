"""A routed-expert (MoE) layer served privately: one chip's share of it.

``PrivateMoE`` serves the mixture-of-experts block of DeepSeek-V3
(``MoEGate`` / ``DeepseekV3MoE``) for the experts it holds, as expert
parallelism gives each chip its share: the router scores every routed
expert, and the layer returns the shared expert's output plus the part
of the routed output that its own experts give.  What the absent
experts would add is left out.  Every product with a private weight
runs through one :class:`~repro.serve.ServingEngine` (one worker pool,
one pipeline session) on that weight's resident residues; the client's
steps between the products run on the host in float64 numpy.

One ``run()`` step folds up to ``max_batch`` queued requests into the
rows ``X`` [T, hidden] of one step, then:

1. router stage: ``logits = X W_r``, one private product of all T rows;
2. routing at the client (``route``), per token row ``x``::

       scores = 1 / (1 + exp(-logits))
       c      = scores + bias                      (bias public)
       group score = sum of the top 2 of c in each of n_group groups
       keep the topk_group best groups; c of the others -> -inf
       idx    = the top_k of c                     (ties: lower index)
       w      = scores[idx] / (sum(scores[idx]) + 1e-20) * scale

   with the sum taken left to right in ``idx`` order and the group ties
   also going to the lower index;
3. gate_up stage: ``g = X G``, ``u = X U`` for the shared expert (all
   T rows) and, for each held expert ``e`` that some row picked, of the
   rows that picked it; an expert no row picked is skipped;
4. SwiGLU at the client (``swiglu_scaled``), per row of each pair::

       h  = g / (1 + exp(-g)) * u
       j  = e + 1, where max|h| = f 2^e with f in [0.5, 1)
       h' = h 2^-j                                 (max|h'| in [0.25, 0.5))

   so that the down product stays inside the engine's no-wrap bound at
   the scale it picks;
5. down stage: ``d = h' D`` for the shared expert and each held expert;
6. combine at the client: ``y = d_shared 2^j``, then for each held
   expert in ascending index ``y[rows_e] += w_e (d_e 2^j_e)``.

Each private product is the engine's exact fixed-point product: the
request's operands rounded at the engine's power-of-two scale and
multiplied exactly in GF(p).  So the served ``y`` is the exact
fixed-point layer, not the float layer.

Row counts vary with the routing, so the engine runs on a ladder of row
buckets; ``warm()`` compiles every program the layer can launch
(each weight shape at each rung and padded batch) before a timed
window.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from ..obs.tracer import TRACER
from .engine import ServingEngine
from .request import DONE, QUEUED

ROUTER = "router"
SHARED = "shared"
PARTS = ("gate", "up", "down")


def weight_name(expert: Optional[int], part: str) -> str:
    """The engine name of an expert's ``part``; ``expert=None`` is the
    shared expert."""
    return f"{SHARED}.{part}" if expert is None else f"expert.{expert}.{part}"


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """The router's published settings (DeepSeek-V3: 256, 8, 8, 4, 2.5)."""

    n_experts: int  # routed experts the router scores, held here or not
    top_k: int
    n_group: int
    topk_group: int
    routed_scaling_factor: float

    def __post_init__(self):
        if self.n_experts % self.n_group:
            raise ValueError(
                f"n_group={self.n_group} must divide n_experts={self.n_experts}"
            )
        if self.top_k > self.n_experts // self.n_group * self.topk_group:
            raise ValueError("top_k exceeds the experts of the kept groups")


def route(logits: np.ndarray, bias: np.ndarray, cfg: MoEConfig) -> Tuple[np.ndarray, ...]:
    """(idx [T, top_k], w [T, top_k], scores [T, n_experts]): the
    ``noaux_tc`` top-k of the module docstring, in float64."""
    scores = 1.0 / (1.0 + np.exp(-logits))
    c = scores + bias
    rows = c.shape[0]
    top2 = np.sort(c.reshape(rows, cfg.n_group, -1), axis=-1)[..., -2:]
    group_score = top2[..., 0] + top2[..., 1]
    groups = np.argsort(-group_score, axis=-1, kind="stable")[:, : cfg.topk_group]
    keep = np.zeros((rows, cfg.n_group), bool)
    np.put_along_axis(keep, groups, True, axis=-1)
    keep = np.repeat(keep, cfg.n_experts // cfg.n_group, axis=-1)
    idx = np.argsort(-np.where(keep, c, -np.inf), axis=-1, kind="stable")[:, : cfg.top_k]
    picked = np.take_along_axis(scores, idx, axis=-1)
    total = picked[:, 0]
    for i in range(1, cfg.top_k):
        total = total + picked[:, i]
    w = picked / (total[:, None] + 1e-20) * cfg.routed_scaling_factor
    return idx, w, scores


def swiglu_scaled(g: np.ndarray, u: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """(h' [rows, width], j [rows]): SwiGLU, each row scaled by 2^-j."""
    with np.errstate(over="ignore"):  # exp(-g) = inf gives silu(g) = -0.0
        h = g / (1.0 + np.exp(-g)) * u
    j = np.frexp(np.abs(h).max(axis=1))[1] + 1
    return np.ldexp(h, -j[:, None]), j


@dataclasses.dataclass
class MoERequest:
    """One client request: ``x`` [rows, hidden] through the layer."""

    rid: int
    x: np.ndarray
    state: str = QUEUED
    y: Optional[np.ndarray] = None  # [rows, hidden], this chip's share
    replay: int = -1  # the last session replay of the step that served it


class PrivateMoE:
    """The held share of a routed-expert layer behind one serving engine.

    ``weights``: ``{name: matrix}`` with ``"router"`` [hidden, n_experts],
    the shared expert's and each held expert's ``gate``/``up`` [hidden,
    width] and ``down`` [width, hidden], named by ``weight_name``.
    ``held``: the routed experts this chip holds.  ``bias``: the
    router's public ``e_score_correction_bias`` [n_experts].
    ``max_batch`` client requests make one step; ``row_buckets`` is the
    engine's ladder, whose top rung bounds a step's rows and a replay's
    (``ServingEngine(max_rows=)``: a product of a whole step rides
    alone, since a few would run the chip out of memory); ``engine_kw``
    goes to the ``ServingEngine``.
    """

    def __init__(
        self,
        weights: Mapping[str, np.ndarray],
        held: Sequence[int],
        cfg: MoEConfig,
        traces,
        config=None,
        *,
        bias: np.ndarray,
        row_buckets: Sequence[int],
        max_batch: int = 8,
        **engine_kw,
    ):
        self.held = tuple(sorted(int(e) for e in held))
        if not self.held or self.held[0] < 0 or self.held[-1] >= cfg.n_experts:
            raise ValueError(f"held experts {held} outside 0..{cfg.n_experts - 1}")
        self.cfg = cfg
        self.bias = np.asarray(bias, np.float64)
        if self.bias.shape != (cfg.n_experts,):
            raise ValueError(f"bias must be [{cfg.n_experts}], got {self.bias.shape}")
        names = [ROUTER] + [
            weight_name(e, part) for e in (None,) + self.held for part in PARTS
        ]
        missing = [n for n in names if n not in weights]
        if missing:
            raise ValueError(f"missing weights {missing}")
        self.hidden = int(weights[ROUTER].shape[0])
        if weights[ROUTER].shape != (self.hidden, cfg.n_experts):
            raise ValueError(
                f"router must be [hidden, {cfg.n_experts}], got {weights[ROUTER].shape}"
            )
        self.max_batch = int(max_batch)
        self.max_rows = max(row_buckets)
        self.engine = ServingEngine(
            {n: weights[n] for n in names}, traces, config,
            row_buckets=row_buckets, max_rows=self.max_rows, **engine_kw,
        )
        self._queue: List[MoERequest] = []
        self._next_rid = 0

    # -- client side -----------------------------------------------------

    def submit(self, x: np.ndarray, arrival: float = 0.0) -> MoERequest:
        """Queue ``x`` [rows, hidden]; ``arrival`` is accepted for the
        engine's signature and unused (a step starts when ``run()`` is
        called)."""
        x = np.asarray(x, np.float64)
        if x.ndim != 2 or x.shape[1] != self.hidden or not 1 <= x.shape[0] <= self.max_rows:
            raise ValueError(
                f"x must be [1..{self.max_rows}, hidden={self.hidden}], got {x.shape}"
            )
        req = MoERequest(self._next_rid, x)
        self._next_rid += 1
        self._queue.append(req)
        return req

    def run(self) -> None:
        """Serve every queued request, up to ``max_batch`` a step and no
        more rows a step than the top rung."""
        while self._queue:
            step, rows = [], 0
            for r in self._queue:
                if len(step) == self.max_batch or rows + len(r.x) > self.max_rows:
                    break
                step.append(r)
                rows += len(r.x)
            del self._queue[: len(step)]
            self._step(step)

    def _step(self, reqs: List[MoERequest]) -> None:
        x = np.concatenate([r.x for r in reqs])
        (logits,), _ = self._stage("router", [(ROUTER, x)])
        with TRACER.span("serve.moe.route") as sp:
            idx, w, scores = route(logits, self.bias, self.cfg)
            picks: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
            for e in self.held:
                rows, slot = np.nonzero(idx == e)  # rows ascending, one slot each
                if len(rows):
                    picks[e] = (rows, w[rows, slot])
            sp.set(
                held_rows=int(sum(len(r) for r, _ in picks.values())),
                expert_rows=[len(picks[e][0]) if e in picks else 0 for e in self.held],
                saturated=int(np.count_nonzero((scores == 0.0) | (scores == 1.0))),
            )
        experts = [None] + sorted(picks)
        inputs = [x] + [x[picks[e][0]] for e in experts[1:]]
        gu, _ = self._stage("gate_up", [
            (weight_name(e, part), xe)
            for e, xe in zip(experts, inputs) for part in ("gate", "up")
        ])
        with TRACER.span("serve.moe.swiglu"):
            scaled = [swiglu_scaled(g, u) for g, u in zip(gu[0::2], gu[1::2])]
        down, replay = self._stage("down", [
            (weight_name(e, "down"), h) for e, (h, _) in zip(experts, scaled)
        ])
        with TRACER.span("serve.moe.combine"):
            y = np.ldexp(down[0], scaled[0][1][:, None])
            for e, d, (_, j) in zip(experts[1:], down[1:], scaled[1:]):
                rows, we = picks[e]
                y[rows] += we[:, None] * np.ldexp(d, j[:, None])
        at = 0
        for r in reqs:
            r.y = y[at:at + len(r.x)]
            at += len(r.x)
            r.state = DONE
            r.replay = replay

    def _stage(self, stage: str, entries) -> Tuple[List[np.ndarray], int]:
        """One private product per ``(weight, rows)`` entry, served by the
        engine's replays; (answers in entry order, last replay index)."""
        eng = self.engine
        served, launched = eng.rows_served, eng.rows_launched
        with TRACER.span(
            "serve.moe.stage", stage=stage, weights=len(entries),
            rows=int(sum(len(x) for _, x in entries)),
        ) as sp:
            reqs = [eng.submit(x, 0.0, weight=name) for name, x in entries]
            eng.run()
            rows = eng.rows_launched - launched
            sp.set(launched_rows=rows, padded_rows=rows - (eng.rows_served - served))
        failed = [r.rid for r in reqs if r.state != DONE]
        if failed:
            raise RuntimeError(f"{stage}: engine requests {failed} were not served")
        eng.forget(reqs)
        return [r.y for r in reqs], max(r.replay for r in reqs)

    # -- set-up ----------------------------------------------------------

    def launch_shapes(self) -> List[tuple]:
        """Every ``(k, out, rows, batch)`` a step can launch, in ascending
        order: per stage and rung, for each count of products the stage
        can give (router 1; gate/up 2 per expert served, the shared one
        included; down 1 per expert served), full replays at the rung's
        batch cap and the rest at the smallest power of two that holds
        it, or the cap.  The engine pads a batch to the smallest it has
        launched, so once ``warm`` has launched these, a step launches
        no other."""
        eng, n = self.engine, len(self.held) + 1
        counts = {ROUTER: [1], "gate": range(2, 2 * n + 1, 2), "down": range(1, n + 1)}
        shapes = set()
        for key, name in ((ROUTER, ROUTER), ("gate", weight_name(None, "gate")),
                          ("down", weight_name(None, "down"))):
            k, out = eng.weights[name].shape
            for rows in eng.row_buckets:
                cap = eng.batch_cap(rows)
                for c in counts[key]:
                    full, rest = divmod(c, cap)
                    if full:
                        shapes.add((k, out, rows, cap))
                    if rest:
                        shapes.add((k, out, rows, min(cap, 1 << (rest - 1).bit_length())))
        return sorted(shapes)

    def warm(self, seed: int = 0) -> int:
        """Launch every shape of ``launch_shapes`` once, in its order (a
        larger batch launched first would take a smaller one's place),
        on seeded data of the range the layer's own inputs take
        (activations in [-1, 1), the down products' inputs in
        [-1/2, 1/2)), cycling through the weights of each shape, whose
        residues upload on the way.  Returns the replays launched."""
        eng = self.engine
        rng = np.random.default_rng(seed)
        by_shape: Dict[tuple, List[str]] = {}
        for name, m in eng.weights.items():
            by_shape.setdefault(m.shape, []).append(name)
        turn = {shape: 0 for shape in by_shape}
        replays = 0
        for k, out, rows, b in self.launch_shapes():
            names = by_shape[(k, out)]
            amp = 0.5 if names[0].endswith(".down") else 1.0
            reqs = []
            for _ in range(b):
                name = names[turn[(k, out)] % len(names)]
                turn[(k, out)] += 1
                reqs.append(eng.submit(rng.uniform(-amp, amp, (rows, k)), 0.0, weight=name))
            eng.run()
            eng.forget(reqs)
            replays += 1
        return replays
