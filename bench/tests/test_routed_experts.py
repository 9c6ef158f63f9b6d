"""The ``routed_experts`` layer through ``load_cell`` and ``run``, at a tiny
size on the CPU: one configuration of the DeepSeek-V3 MoE layer's shape
cut to hidden 64, expert width 32, 16 routed experts (4 held), top 4."""
import io
import json
import os
import time

import numpy as np

from bench import reference
from bench.harness import run
from bench.run import load_cell
from bench.workload import Activations

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
CONFIG = os.path.join(BENCH_DIR, "configs", "deepseek-v3-moe.age-s2t2z2.json")
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
SEED = 2 ** 33 + 29


def _cell(tmp_path):
    with open(CONFIG) as f:
        d = json.load(f)
    d.update(
        hidden_size=64, moe_intermediate_size=32, n_routed_experts=4,
        num_experts_per_tok=4, n_group=4, topk_group=2,
        published={"num_hidden_layers": 61, "n_routed_experts": 16},
        deployment=dict(d["deployment"], held_experts=[8, 9, 10, 11]),
        engine={"row_buckets": [32, 128]},
        pool=dict(d["pool"], traces=3), data=dict(d["data"], router_nonzeros=8),
    )
    path = tmp_path / "moe.json"
    path.write_text(json.dumps(d))
    spec = {
        "configs": [{"name": "c", "file": str(path)}],
        "workloads": [{"name": "w", "config": "c", "traffic": "closed16", "chips": 1}],
        "end_to_end": [{"name": "rows_per_s", "unit": "rows/s"}],
        "per_layer": [{"name": n, "unit": "s"} for n in (
            "serve.moe.client_s_per_step", "serve.moe.expert_stages_s_per_step",
            "serve.moe.pad_share")],
    }
    cell = load_cell(spec, "w", BENCH_DIR)
    assert cell.layer.__file__ == os.path.join(BENCH_DIR, "layers", "routed_experts.py")
    return cell


def _run(cell, tmp_path, trace=False):
    out, err = io.StringIO(), io.StringIO()
    rc = run(cell, SEED, 0.5, trace, time.perf_counter(), str(tmp_path / "trace"),
             require_tpu=False, peaks=PEAKS, out=out, err=err)
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    assert rc == (0 if result["correct"] else 1)
    return result, err.getvalue()


def test_the_moe_layer_runs_correct_with_nothing_built_in_the_window(tmp_path):
    result, err = _run(_cell(tmp_path), tmp_path)
    assert result["correct"] is True
    # 16 clients x 16 rows: each run() is two steps of 8
    assert result["attempted"] >= 16 and result["failed"] == 0
    assert result["compared"]["mismatched_elements"] == {"value": 0, "limit": 0}
    assert result["metrics"]["rows_per_s"]["value"] > 0
    assert "executables built or loaded inside the window: 0 " in err


def test_traced_run_reads_the_three_moe_metrics(tmp_path, monkeypatch):
    import jax

    # the CPU profiler's trace holds no device plane for the harness to read
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    from bench import trace_reduce

    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: None)
    monkeypatch.setattr(trace_reduce, "read_trace", lambda path: _NoDevice())
    result, _ = _run(_cell(tmp_path), tmp_path, trace=True)
    metrics = result["metrics"]
    assert set(metrics) == {"serve.moe.client_s_per_step",
                            "serve.moe.expert_stages_s_per_step", "serve.moe.pad_share"}
    assert all(m["value"] > 0 for m in metrics.values())
    assert metrics["serve.moe.pad_share"]["value"] < 1


class _NoDevice:
    window = (0.0, 1.0)
    chips = []
    window_s = 1.0

    def idle_gaps(self, chip):
        return []

    def top_ops(self, n):
        return []

    def busy_s(self, chips):
        return 0.0


def test_the_control_fails_on_every_request(tmp_path):
    cell = _cell(tmp_path)
    layer, dep = cell.layer, cell.deployment
    weights = layer.make_weights(dep, SEED)
    acts = Activations(SEED, 16, dep.in_width)
    xs = [acts.next() for _ in range(8)]
    refs = layer.reference(dep, weights, xs)
    bad = reference.mismatches(layer.control(dep, weights, xs), refs)
    assert all(b > 0 for b in bad)


def test_a_planted_fault_in_one_product_is_not_correct(tmp_path, monkeypatch):
    import repro.runtime.pipeline as pipeline

    real = pipeline._unfold_batched_y
    calls = []

    def altered(plan, coeffs, batch):
        y = np.array(real(plan, coeffs, batch))
        calls.append(1)
        # one element of one product of a step inside the window, by
        # enough that the client's rounding downstream cannot absorb it
        if len(calls) == 40:
            y[0, 0, 0] = (y[0, 0, 0] + 20000) % 65521
        return y

    monkeypatch.setattr(pipeline, "_unfold_batched_y", altered)
    result, _ = _run(_cell(tmp_path), tmp_path)
    assert result["correct"] is False
    assert result["compared"]["mismatched_elements"]["value"] > 0
