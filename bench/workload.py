"""What every layer draws from the seed: activations and seed streams.

A layer (``bench/layers/<name>.py``) makes its weights, engine and pool
traces from ``--seed`` through ``_seed32`` and the streams below; the
harness draws each request's activations from ``Activations``, one
stream in submission order.  ``Deployment``, ``make_weights`` and
``make_engine`` of the single projection live in
``bench/layers/projection.py`` and are re-exported here.
"""
from __future__ import annotations

import numpy as np

# Distinct streams drawn from one seed.
_W, _X, _TRACES, _ENGINE = range(4)


def _seed32(seed: int, *stream: int) -> int:
    """A 31-bit seed for one stream of ``seed`` (any size of ``seed``)."""
    return int(np.random.SeedSequence([seed, *stream]).generate_state(1)[0] >> 1)


class Activations:
    """Request activations ``[rows, width]`` in submission order, from the seed."""

    def __init__(self, seed: int, rows: int, width: int, stream: int = _X):
        self._rng = np.random.default_rng(np.random.SeedSequence([seed, stream]))
        self.rows, self.width = rows, width

    def next(self) -> np.ndarray:
        return self._rng.uniform(-1.0, 1.0, size=(self.rows, self.width))


_PROJECTION = ("Deployment", "make_weights", "make_engine")


def __getattr__(name: str):
    # loaded on first use: bench.layers.projection imports this module
    if name in _PROJECTION:
        from bench.layers import projection

        return getattr(projection, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
