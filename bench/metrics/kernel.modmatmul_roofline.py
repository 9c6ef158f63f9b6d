"""Share of its roofline the Pallas ``modmatmul`` kernel reaches, in %.

For every call of the kernel in the traced window (found by the
instruction name ``modmatmul_pallas``), the least time the chip needs is
the larger of the compute bound (field multiply-adds x 2 over the bf16
peak) and the memory bound (operand and result elements at 2 bytes over
HBM bandwidth), counted by ``bench.counts`` from the call's shapes, with
tile padding taken back off by the worker products the cell's layer
defines (``worker_products``).  The share is the summed least time over
the summed device time of those calls.
"""
from bench import counts, trace_reduce
from bench.harness import KERNEL


def read(ctx):
    if ctx.device is None:
        return None
    cell = ctx.cell
    logical = cell.layer.worker_products(cell.deployment, cell.traffic.rows)
    least = spent = 0.0
    for _chip, hlo, dur_ns in ctx.device.events_named(KERNEL):
        ops = trace_reduce.custom_call_operands(hlo)
        call = counts.call_from_operands(*ops[:2]) if len(ops) >= 2 else None
        if call is None:
            return None  # a call whose work cannot be counted: no share
        least += counts.least_time_s(counts.unpad(call, logical), ctx.peaks)[0]
        spent += dur_ns * 1e-9
    if spent <= 0:
        return None
    return 100.0 * least / spent
