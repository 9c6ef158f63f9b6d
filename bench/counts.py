"""Work the algorithm needs, counted from shapes: the roofline's numerator.

These counts belong to the benchmark, not to the program, so a change to
how the program implements a product cannot change what it is measured
against.  A GF(p) multiply-add is priced at one bf16 MXU multiply-add
(two flops): no implementation does it in less.  A field element moves
at 2 bytes, the least that holds a value below 65521.
"""
from __future__ import annotations

import json
import math
import os
from dataclasses import dataclass
from typing import Optional, Sequence

FIELD_ELEM_BYTES = 2
PEAKS_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


@dataclass(frozen=True)
class MatmulCall:
    """One ``[B?, M, K] @ [B?, K, N]`` modular product.

    ``a_batched`` / ``b_batched`` say whether that operand carries the
    batch axis; an unbatched operand is read once for the whole call.
    """

    batch: int
    m: int
    k: int
    n: int
    a_batched: bool = True
    b_batched: bool = True

    @property
    def field_macs(self) -> int:
        return self.batch * self.m * self.k * self.n

    @property
    def least_bytes(self) -> int:
        a = self.m * self.k * (self.batch if self.a_batched else 1)
        b = self.k * self.n * (self.batch if self.b_batched else 1)
        out = self.batch * self.m * self.n
        return FIELD_ELEM_BYTES * (a + b + out)


def load_peaks(device_kind: str, path: str = PEAKS_PATH) -> dict:
    """The peak rates of one chip of ``device_kind``; unknown kinds raise."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in {path}; add its peaks "
            f"with their source (known: {sorted(table)})"
        )
    return table[device_kind]


def least_time_s(call: MatmulCall, peaks: dict) -> tuple:
    """(seconds, bound) the chip needs at least for ``call``; ``bound`` is
    ``"compute"`` or ``"memory"``, whichever is larger."""
    compute = 2.0 * call.field_macs / peaks["bf16_flops_per_s"]
    memory = call.least_bytes / peaks["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")


def request_flops(rows: int, k: int, out: int) -> int:
    """Plaintext FLOPs of one request ``[rows, k] @ [k, out]``: what the
    user asks for, with no protocol overhead."""
    return 2 * rows * k * out


PAD_MAX = 512  # a launched dim more than this above the product's is not padding


def worker_product(k: int, out: int, rows: int, s: int, t: int) -> tuple:
    """Logical ``(m, k, n)`` of one worker's multiply under an s x t
    split: ``A`` blocks ``[rows / t, k / s]`` times ``W`` blocks
    ``[k / s, out / t]``, as the coded-computing scheme defines them."""
    return rows // t, k // s, out // t


def unpad(call: MatmulCall, logical: Sequence[tuple]) -> MatmulCall:
    """Map a kernel call's launched shapes back to the product the
    algorithm asks for: the first ``(m, k, n)`` of ``logical`` that each
    launched dim holds with less than ``PAD_MAX`` to spare.  A call none
    explains keeps its shapes as launched."""
    launched = (call.m, call.k, call.n)
    for dims in logical:
        if all(x <= d < x + PAD_MAX for x, d in zip(dims, launched)):
            return MatmulCall(call.batch, *dims, call.a_batched, call.b_batched)
    return call


def call_from_operands(a_shape: Sequence[int], b_shape: Sequence[int]) -> Optional[MatmulCall]:
    """The product ``a @ b`` of two operand shapes as a kernel launches it
    (2D or 3D each), or None when they do not form one."""
    if len(a_shape) not in (2, 3) or len(b_shape) not in (2, 3):
        return None
    m, k = a_shape[-2:]
    k2, n = b_shape[-2:]
    if k != k2:
        return None
    a_b = len(a_shape) == 3
    b_b = len(b_shape) == 3
    batch = a_shape[0] if a_b else (b_shape[0] if b_b else 1)
    if a_b and b_b and a_shape[0] != b_shape[0]:
        return None
    return MatmulCall(batch, m, k, n, a_b, b_b)


def percentile(values: Sequence[float], q: float) -> float:
    """Linear-interpolated percentile (numpy's default rule), pure Python."""
    xs = sorted(values)
    if not xs:
        return math.nan
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)
