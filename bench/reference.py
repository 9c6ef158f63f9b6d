"""Plain reference for one served request, and its lower-precision control.

The served layer computes ``Y = X W`` of fixed-point-rounded operands
over GF(p) and returns ``Y`` as reals.  The reference does the same
arithmetic in the plainest way, importing nothing of the program:

* the scale: the largest power of two ``S`` with
  ``k * (2 S max|X|) * (2 S max|W|) < (p - 1) / 2``, so the signed
  product of the rounded operands cannot wrap mod p (the rule the
  deployment states; written out here, not imported);
* ``Xq = rint(S X)``, ``Wq = rint(S W)``; their product in float64, which
  is exact because every partial sum stays below 2**53;
* ``Y = (Xq Wq) / S**2``.

An exact comparison with the served ``Y`` is therefore possible and its
limit is 0.

The control is the same product put in the program's place and computed
one step below the precision the deployment states: the field
representatives in ``[0, p)`` multiplied as float32 (``HIGHEST``
precision on the chip), then reduced mod p and lifted back to signed
values.  That is the shortcut a later change could be tempted by
(dropping the exact limb arithmetic for a plain float32 matmul); its
results must fail the comparison.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

ROW_BLOCK = 1024  # rows per float64 matmul: bounds the reference's host memory


def scale_for(k: int, x_max: float, w_max: float, p: int) -> int:
    half = (p - 1) // 2
    s = 1
    while k * (x_max * 2 * s) * (w_max * 2 * s) < half:
        s *= 2
    return s


def _scales(xs: Sequence[np.ndarray], w: np.ndarray, p: int) -> List[int]:
    k = w.shape[0]
    w_max = float(np.abs(w).max() + 1e-9)
    return [scale_for(k, float(np.abs(x).max() + 1e-9), w_max, p) for x in xs]


def reference(xs: Sequence[np.ndarray], w: np.ndarray, p: int) -> List[np.ndarray]:
    """Exact ``Y`` of every request ``x`` in ``xs`` against ``w``."""
    return _blocked(xs, w, p, _exact_product)


def control(xs: Sequence[np.ndarray], w: np.ndarray, p: int) -> List[np.ndarray]:
    """The reference with its product taken in float32 over the field."""
    return _blocked(xs, w, p, _float32_field_product)


def _blocked(xs, w, p, product) -> List[np.ndarray]:
    scales = _scales(xs, w, p)
    out: List[np.ndarray] = [None] * len(xs)
    by_scale: Dict[int, List[int]] = {}
    for i, s in enumerate(scales):
        by_scale.setdefault(s, []).append(i)
    for s, idx in by_scale.items():
        wq = np.rint(np.asarray(w, np.float64) * s)
        rows = [np.rint(np.asarray(xs[i], np.float64) * s) for i in idx]
        stacked = np.concatenate(rows)
        y = np.concatenate([
            product(stacked[r:r + ROW_BLOCK], wq, p)
            for r in range(0, stacked.shape[0], ROW_BLOCK)
        ])
        at = 0
        for i, xq in zip(idx, rows):
            out[i] = y[at:at + xq.shape[0]] / float(s * s)
            at += xq.shape[0]
    return out


def _exact_product(xq: np.ndarray, wq: np.ndarray, p: int) -> np.ndarray:
    bound = xq.shape[1] * np.abs(xq).max(initial=0) * np.abs(wq).max(initial=0)
    if bound >= 2.0 ** 53:
        raise ValueError("float64 product would not be exact at this size")
    return xq @ wq


def _float32_field_product(xq: np.ndarray, wq: np.ndarray, p: int) -> np.ndarray:
    import jax
    import jax.numpy as jnp

    xf = jnp.asarray(np.mod(xq, p), jnp.float32)
    wf = jnp.asarray(np.mod(wq, p), jnp.float32)
    yf = np.asarray(
        jnp.matmul(xf, wf, precision=jax.lax.Precision.HIGHEST), np.float64
    )
    field = np.mod(np.rint(yf), p)
    return np.where(field > (p - 1) // 2, field - p, field)


def mismatches(ys: Sequence[np.ndarray], refs: Sequence[np.ndarray]) -> List[int]:
    """Per request: elements of ``y`` that differ from the reference
    (a missing or misshapen ``y`` counts every element)."""
    out = []
    for y, ref in zip(ys, refs):
        if y is None or np.shape(y) != ref.shape:
            out.append(int(ref.size))
        else:
            out.append(int(np.count_nonzero(np.asarray(y, np.float64) != ref)))
    return out
