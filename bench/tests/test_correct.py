"""How ``correct`` is decided: the reference, its control, and planted faults.

The runs here drive the whole harness on the CPU at a small size, with
the look for a chip skipped; everything after it is the benchmark's own
code path.
"""
import io
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference
from bench.harness import Cell, run
from bench.traffic import Traffic
from bench.workload import Deployment

P = 65521
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def _int_reference(x, w, p):
    s = reference.scale_for(w.shape[0], np.abs(x).max() + 1e-9, np.abs(w).max() + 1e-9, p)
    xq = np.rint(x * s).astype(object)
    wq = np.rint(w * s).astype(object)
    return (xq.dot(wq)).astype(np.float64) / (s * s)


def test_reference_is_the_exact_fixed_point_product():
    rng = np.random.default_rng(0)
    w = rng.uniform(-1, 1, (256, 96))
    xs = [rng.uniform(-1, 1, (4, 256)) * scale for scale in (1.0, 0.01, 1.0)]
    for y, x in zip(reference.reference(xs, w, P), xs):
        np.testing.assert_array_equal(y, _int_reference(x, w, P))


def test_scales_at_the_cells_widths():
    # uniform data keeps operands on [-2, 2] at both contraction depths...
    assert reference.scale_for(2304, 1.0, 1.0, P) == 2
    assert reference.scale_for(5760, 1.0, 1.0, P) == 2
    # ...while weights of std 1/sqrt(k) take scale 4 and all round to 0
    k = 2304
    w = np.random.default_rng(0).normal(size=(k, 64)) / np.sqrt(k)
    s = reference.scale_for(k, 4.5, float(np.abs(w).max()), P)
    assert s == 4 and not np.rint(w * s).any()


def test_control_fails_the_comparison():
    rng = np.random.default_rng(1)
    w = rng.uniform(-1, 1, (256, 96))
    xs = [rng.uniform(-1, 1, (4, 256)) for _ in range(3)]
    bad = reference.mismatches(reference.control(xs, w, P), reference.reference(xs, w, P))
    assert all(b > 0.5 * 4 * 96 for b in bad)


def test_mismatches_counts_missing_answers_whole():
    ref = [np.zeros((2, 3))]
    assert reference.mismatches([None], ref) == [6]
    assert reference.mismatches([np.ones((2, 3))], ref) == [6]
    assert reference.mismatches([np.zeros((2, 3))], ref) == [0]


def _cell(loop: str, chips: int = 1) -> Cell:
    dep = Deployment.from_dict({
        "name": "small", "projection": "up", "hidden_size": 64, "intermediate_size": 96,
        "scheme": {"method": "age", "s": 2, "t": 2, "z": 2}, "field_p": P,
        "max_batch": 2,
        "pool": {"spares": 4, "compute_latency": {"shift": 0.1, "scale": 0.5},
                 "net_scale": 0.3, "traces": 3},
    })
    if loop == "closed":
        traffic = Traffic("closed", 4, clients=4)
    else:
        traffic = Traffic("open", 4, rate_per_s=20.0)
    return Cell("small", chips, dep, traffic, [], [])


def _run(cell: Cell, tmp_path, seconds: float = 0.4) -> dict:
    out, err = io.StringIO(), io.StringIO()
    rc = run(cell, 2 ** 33 + 17, seconds, False, time.perf_counter(),
             str(tmp_path / "trace"), require_tpu=False, peaks=PEAKS, out=out, err=err)
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    assert err.getvalue().strip().splitlines()[-1].startswith("correct: ")
    assert list(result)[-1] == "compared"
    assert rc == (0 if result["correct"] else 1)
    return result


@pytest.mark.parametrize("loop,chips", [("closed", 1), ("open", 1), ("closed", 4)])
def test_a_sound_run_is_correct(loop, chips, tmp_path):
    result = _run(_cell(loop, chips), tmp_path)
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["compared"]["mismatched_elements"] == {"value": 0, "limit": 0}


def _alter_one_answer(monkeypatch):
    import repro.runtime.pipeline as pipeline

    real = pipeline._unfold_batched_y

    def altered(plan, coeffs, batch):
        y = np.array(real(plan, coeffs, batch))
        y[0, 0, 0] = (y[0, 0, 0] + 1) % P
        return y

    monkeypatch.setattr(pipeline, "_unfold_batched_y", altered)


def _leave_out_half_the_batch(monkeypatch):
    from repro.serve import ServingEngine

    real = ServingEngine._admit

    def half(self, t_launch):
        batch = real(self, t_launch)
        return batch[: max(1, len(batch) // 2)]  # the rest is never served

    monkeypatch.setattr(ServingEngine, "_admit", half)


def _return_state_unchanged(monkeypatch):
    from repro.runtime.pipeline import PipelineSession

    real = PipelineSession._append
    first = {}

    def stale(self, *args):
        replay = real(self, *args)
        y = first.setdefault(np.shape(replay.y), replay.y)
        replay.y = y  # every later replay hands back the first one's answers
        return replay

    monkeypatch.setattr(PipelineSession, "_append", stale)


def _leave_out_the_exchange(monkeypatch):
    import repro.core.distributed as distributed

    def local_only(x, axis_name, split_axis, concat_axis, tiled):
        nloc, npad = x.shape[0], x.shape[1]
        d = npad // nloc
        chunks = x.reshape((nloc, d, npad // d) + x.shape[2:])
        return jnp.moveaxis(chunks, 1, 0).reshape((npad, npad // d) + x.shape[2:])

    distributed._phase2_program.cache_clear()
    monkeypatch.setattr(jax.lax, "all_to_all", local_only)


FAULTS = {
    "answer_altered": (_alter_one_answer, "closed", 1),
    "half_the_batch_left_out": (_leave_out_half_the_batch, "closed", 1),
    "state_returned_unchanged": (_return_state_unchanged, "open", 1),
    "exchange_left_out": (_leave_out_the_exchange, "closed", 4),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_makes_the_run_incorrect(fault, monkeypatch, tmp_path):
    plant, loop, chips = FAULTS[fault]
    plant(monkeypatch)
    try:
        result = _run(_cell(loop, chips), tmp_path)
    finally:
        import repro.core.distributed as distributed

        distributed._phase2_program.cache_clear()
        jax.clear_caches()
    assert result["correct"] is False
    assert result["failed"] > 0
    compared = result["compared"]
    assert compared["mismatched_elements"]["value"] + compared["missing_requests"]["value"] > 0
