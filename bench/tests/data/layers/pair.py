"""A toy layer of two weights, for ``test_layers.py``: each request's answer is
``[X W1, X W2]`` side by side, served by two ``ServingEngine``s.

It lives outside ``bench/layers/`` and reaches the harness only through
the directory a test passes to ``load_cell``: proof that a layer added as
a new file needs no edit to the harness, ``control.py`` or a reader.  Its
configuration is a projection's, with ``"widths"`` in place of one
``intermediate_size``: one up-projection of ``hidden_size`` to each.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, List, Sequence

import numpy as np

from bench.layers import projection


@dataclass(frozen=True)
class Pair:
    name: str
    p: int
    max_batch: int
    halves: tuple  # one projection.Deployment per weight

    @property
    def in_width(self) -> int:
        return self.halves[0].in_width


def from_dict(d: dict) -> Pair:
    halves = tuple(
        projection.from_dict(dict(d, name=f"{d['name']}.{i}", projection="up",
                                  intermediate_size=n))
        for i, n in enumerate(d["widths"])
    )
    return Pair(d["name"], halves[0].p, halves[0].max_batch, halves)


def make_weights(dep: Pair, seed: int) -> List[np.ndarray]:
    return [projection.make_weights(h, seed + i) for i, h in enumerate(dep.halves)]


class PairRequest:
    def __init__(self, parts: Sequence[Any]):
        self.parts = list(parts)

    @property
    def x(self) -> np.ndarray:
        return self.parts[0].x

    @property
    def y(self):
        ys = [r.y for r in self.parts]
        return None if any(y is None for y in ys) else np.concatenate(ys, axis=1)

    @property
    def state(self) -> str:
        from repro.serve import DONE

        return next((r.state for r in self.parts if r.state != DONE), DONE)

    @property
    def replay(self) -> int:
        return self.parts[0].replay


class PairEngine:
    def __init__(self, engines: Sequence[Any]):
        self.engines = list(engines)

    def submit(self, x: np.ndarray, arrival: float) -> PairRequest:
        return PairRequest([e.submit(x, arrival) for e in self.engines])

    def run(self) -> None:
        for e in self.engines:
            e.run()


def make_engine(dep: Pair, weights: Sequence[np.ndarray], seed: int, devices) -> PairEngine:
    return PairEngine([
        projection.make_engine(h, w, seed + i, devices)
        for i, (h, w) in enumerate(zip(dep.halves, weights))
    ])


def _side_by_side(answer, dep: Pair, weights, xs) -> List[np.ndarray]:
    parts = [answer(h, w, xs) for h, w in zip(dep.halves, weights)]
    return [np.concatenate(ys, axis=1) for ys in zip(*parts)]


def reference(dep: Pair, weights, xs) -> List[np.ndarray]:
    return _side_by_side(projection.reference, dep, weights, xs)


def control(dep: Pair, weights, xs) -> List[np.ndarray]:
    return _side_by_side(projection.control, dep, weights, xs)


def worker_products(dep: Pair, rows: int) -> List[tuple]:
    return [m for h in dep.halves for m in projection.worker_products(h, rows)]


def request_flops(dep: Pair, rows: int) -> int:
    return sum(projection.request_flops(h, rows) for h in dep.halves)
