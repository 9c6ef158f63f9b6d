"""The layer a configuration names, found by name; and the single projection
moved into ``bench/layers/projection.py`` without a change to what it makes
or what the readers read of it.

``data/layers/pair.py`` is a toy layer of two weights kept outside
``bench/layers/``: it reaches the harness only through the directory the
test hands to ``load_cell``.
"""
import hashlib
import io
import json
import os
import time
from types import SimpleNamespace

import numpy as np
import pytest

from bench import counts, layers, trace_reduce
from bench.harness import Cell, Served, load_metric, run
from bench.run import load_cell
from bench.traffic import Traffic, Window
from bench.workload import Activations, Deployment, make_weights

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
DATA = os.path.join(BENCH_DIR, "tests", "data")
TOY_LAYERS = os.path.join(DATA, "layers")
P = 65521
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
SEED = 2 ** 33 + 17
POOL = {"spares": 4, "compute_latency": {"shift": 0.1, "scale": 0.5}, "net_scale": 0.3,
        "traces": 3}
# test_correct.py's small projection cell
SMALL = {"name": "small", "projection": "up", "hidden_size": 64, "intermediate_size": 96,
         "scheme": {"method": "age", "s": 2, "t": 2, "z": 2}, "field_p": P, "max_batch": 2,
         "pool": POOL}


def _spec(config_file: str) -> dict:
    return {
        "configs": [{"name": "c", "file": config_file}],
        "workloads": [{"name": "w", "config": "c", "traffic": "closed16", "chips": 1}],
        "end_to_end": [{"name": "rows_per_s", "unit": "rows/s"},
                       {"name": "layer_mfu", "unit": "%"}],
        "per_layer": [],
    }


def _write(tmp_path, config: dict) -> str:
    path = tmp_path / "config.json"
    path.write_text(json.dumps(config))
    return str(path)


def test_a_configuration_without_a_layer_serves_the_projection():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for wl in spec["workloads"]:
        cell = load_cell(spec, wl["name"], BENCH_DIR)
        with open(os.path.join(ROOT, next(
                c["file"] for c in spec["configs"] if c["name"] == wl["config"]))) as f:
            assert "layer" not in json.load(f)
        assert cell.layer.__file__ == os.path.join(BENCH_DIR, "layers", "projection.py")
        assert cell.deployment.in_width == (2304 if wl["name"].startswith("up.") else 5760)
    assert layers.known() == ["projection"]


def test_an_unknown_layer_is_refused_with_the_known_ones(tmp_path):
    spec = _spec(_write(tmp_path, dict(SMALL, layer="no_such_layer")))
    with pytest.raises(ValueError, match=r"no_such_layer.*known: \['projection'\]"):
        load_cell(spec, "w", BENCH_DIR)
    with pytest.raises(ValueError, match=r"known: \['pair'\]"):
        load_cell(spec, "w", BENCH_DIR, layers_dir=TOY_LAYERS)


def _toy_cell(tmp_path) -> Cell:
    toy = dict(SMALL, name="toy", layer="pair", widths=[96, 32], max_batch=8)
    del toy["intermediate_size"]
    cell = load_cell(_spec(_write(tmp_path, toy)), "w", BENCH_DIR, layers_dir=TOY_LAYERS)
    assert cell.layer.__file__ == os.path.join(TOY_LAYERS, "pair.py")
    return cell


def _run(cell: Cell, tmp_path) -> dict:
    out, err = io.StringIO(), io.StringIO()
    rc = run(cell, SEED, 0.4, False, time.perf_counter(), str(tmp_path / "trace"),
             require_tpu=False, peaks=PEAKS, out=out, err=err)
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    assert rc == (0 if result["correct"] else 1)
    return result


def test_a_layer_of_two_weights_added_as_a_file_runs_correct(tmp_path):
    cell = _toy_cell(tmp_path)
    result = _run(cell, tmp_path)
    assert result["correct"] is True
    assert result["attempted"] >= 16 and result["failed"] == 0
    assert result["compared"]["mismatched_elements"] == {"value": 0, "limit": 0}
    assert set(result["metrics"]) == {"rows_per_s", "layer_mfu"}
    # the readers count both products: 2 rows 64 (96 + 32) per row
    rows_per_s = result["metrics"]["rows_per_s"]["value"]
    assert result["metrics"]["layer_mfu"]["value"] == pytest.approx(
        100.0 * rows_per_s * 2 * 64 * (96 + 32) / PEAKS["bf16_flops_per_s"])
    # each answer holds both products side by side
    dep, w = cell.deployment, cell.layer.make_weights(cell.deployment, SEED)
    x = Activations(SEED, 16, dep.in_width).next()
    (ref,) = cell.layer.reference(dep, w, [x])
    assert ref.shape == (16, 128)
    (half,) = layers.load("projection").reference(dep.halves[1], w[1], [x])
    np.testing.assert_array_equal(ref[:, 96:], half)


def test_a_layer_of_two_weights_with_one_answer_altered_is_not_correct(
        monkeypatch, tmp_path):
    import repro.runtime.pipeline as pipeline

    real = pipeline._unfold_batched_y

    def altered(plan, coeffs, batch):
        y = np.array(real(plan, coeffs, batch))
        y[0, 0, 0] = (y[0, 0, 0] + 1) % P
        return y

    monkeypatch.setattr(pipeline, "_unfold_batched_y", altered)
    result = _run(_toy_cell(tmp_path), tmp_path)
    assert result["correct"] is False
    assert result["failed"] > 0
    assert result["compared"]["mismatched_elements"]["value"] > 0


def _digest(a: np.ndarray) -> str:
    return hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()


def test_the_projection_makes_the_parents_weights_and_activations():
    # digests recorded on the commit before the layer became a module
    dep = Deployment.from_dict(SMALL)
    w = make_weights(dep, SEED)
    assert (w.shape, w.dtype) == ((64, 96), np.float64)
    assert _digest(w) == "c4fefac742eced0905c03b6ccc5aa10653b094623256b92b2e33e66f267d2189"
    x = Activations(SEED, 4, dep.in_width).next()
    assert _digest(x) == "57f45df203f23367bd90f48b113178f6c94fd82c3f89008c907ec27841b5b0ad"
    # the harness's own route to them, through the loaded layer
    proj = layers.load("projection")
    assert _digest(proj.make_weights(proj.from_dict(SMALL), SEED)) == _digest(w)


def test_readers_read_the_parents_values():
    # values recorded with the readers of the commit before the layer
    # became a module; layer_mfu now sums per-request FLOPs, the same
    # integers taken in another order, so it may differ in the last bit
    proj = layers.load("projection")
    peaks = counts.load_peaks("TPU v5 lite")
    traced = Cell("trace", 1, proj.from_dict(dict(SMALL, hidden_size=512,
                                                  intermediate_size=1200)),
                  Traffic("closed", 16, clients=16), [], [])
    ctx = SimpleNamespace(
        cell=traced, peaks=peaks,
        device=trace_reduce.read_trace(os.path.join(DATA, "modmatmul_small.xplane.pb")),
    )
    roofline = load_metric("kernel.modmatmul_roofline", "%")
    assert roofline.read(ctx) == 28.630939555309304

    with open(os.path.join(BENCH_DIR, "configs", "minicpm2b-mlp-up.age-s2t2z2.json")) as f:
        up = proj.from_dict(json.load(f))
    done = [Served(due=100.0, run_start=100.0, completion=101.0, rows=16, replay=i // 8)
            for i in range(1497)]
    ctx = SimpleNamespace(
        cell=Cell("up", 1, up, Traffic("closed", 16, clients=16), [], []), peaks=peaks,
        window=Window(t0=100.0, t1=151.267018267), done=done,
    )
    assert load_metric("layer_mfu", "%").read(ctx) == pytest.approx(
        0.006294662527099712, rel=1e-12)
