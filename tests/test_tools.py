"""Repo tooling: the benchmark drift diff (`tools/bench_diff.py`)."""
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))

import bench_diff  # noqa: E402


def _write(root, rel, text):
    path = os.path.join(root, rel)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as fh:
        fh.write(text)


# ----------------------------------------------------------------------
# bench diff
# ----------------------------------------------------------------------
def test_flatten_paths():
    flat = bench_diff.flatten({"a": {"b": [1.0, {"c_us": 2.0}]}, "d": "x"})
    assert flat == {"a.b[0]": 1.0, "a.b[1].c_us": 2.0, "d": "x"}


def test_leaf_classification():
    assert bench_diff.is_wallclock("kernel.total_us")
    assert bench_diff.is_wallclock("batched.us_per_product[3]")
    # the marker may sit on a parent key: phases_us.* are timings
    assert bench_diff.is_wallclock("phases_us.reduce")
    assert bench_diff.is_ratio("pipelined.age.speedup")
    assert not bench_diff.is_wallclock("scheme.n_workers")
    assert not bench_diff.is_ratio("scheme.n_workers")


def _git_repo_with_baseline(tmp_path, baseline):
    root = str(tmp_path)
    env = dict(os.environ, GIT_AUTHOR_NAME="t", GIT_AUTHOR_EMAIL="t@t",
               GIT_COMMITTER_NAME="t", GIT_COMMITTER_EMAIL="t@t")
    for cmd in (["git", "init", "-q"],
                ["git", "add", "-A"],
                ["git", "commit", "-q", "-m", "baseline"]):
        if cmd[1] == "add":
            _write(root, "BENCH.json", json.dumps(baseline))
        subprocess.run(cmd, cwd=root, env=env, check=True,
                       capture_output=True)
    return root


BASELINE = {
    "deterministic": {"n_workers": 17, "speedup": 2.8},
    "timing": {"total_us": 100.0, "decode_us": 40.0, "share_us": 10.0},
}


def test_bench_diff_passes_uniform_machine_speed_shift(tmp_path):
    root = _git_repo_with_baseline(tmp_path, BASELINE)
    fresh = json.loads(json.dumps(BASELINE))
    for k in fresh["timing"]:
        fresh["timing"][k] *= 2.0  # a uniformly slower machine
    _write(root, "BENCH.json", json.dumps(fresh))
    assert bench_diff.diff_file(root, "BENCH.json", "HEAD", band=2.5) == []


def test_bench_diff_catches_deterministic_change(tmp_path):
    root = _git_repo_with_baseline(tmp_path, BASELINE)
    fresh = json.loads(json.dumps(BASELINE))
    fresh["deterministic"]["n_workers"] = 18
    _write(root, "BENCH.json", json.dumps(fresh))
    problems = bench_diff.diff_file(root, "BENCH.json", "HEAD", band=2.5)
    assert any("n_workers" in p for p in problems)


def test_bench_diff_catches_wallclock_outlier(tmp_path):
    root = _git_repo_with_baseline(tmp_path, BASELINE)
    fresh = json.loads(json.dumps(BASELINE))
    fresh["timing"]["decode_us"] *= 50.0  # one leaf regresses alone
    _write(root, "BENCH.json", json.dumps(fresh))
    problems = bench_diff.diff_file(root, "BENCH.json", "HEAD", band=2.5)
    assert any("decode_us" in p for p in problems)


def test_bench_diff_catches_ratio_drift(tmp_path):
    root = _git_repo_with_baseline(tmp_path, BASELINE)
    fresh = json.loads(json.dumps(BASELINE))
    fresh["deterministic"]["speedup"] = 0.5  # 5.6x off, outside the band
    _write(root, "BENCH.json", json.dumps(fresh))
    problems = bench_diff.diff_file(root, "BENCH.json", "HEAD", band=2.5)
    assert any("speedup" in p for p in problems)


def test_bench_diff_catches_shape_change(tmp_path):
    root = _git_repo_with_baseline(tmp_path, BASELINE)
    fresh = json.loads(json.dumps(BASELINE))
    del fresh["timing"]["share_us"]
    fresh["new_section"] = {"x": 1}
    _write(root, "BENCH.json", json.dumps(fresh))
    problems = bench_diff.diff_file(root, "BENCH.json", "HEAD", band=2.5)
    assert any("share_us" in p for p in problems)
    assert any("new_section" in p for p in problems)


def test_bench_diff_skips_missing_baseline(tmp_path):
    root = _git_repo_with_baseline(tmp_path, BASELINE)
    _write(root, "OTHER.json", json.dumps({"a": 1}))
    assert bench_diff.diff_file(root, "OTHER.json", "HEAD", band=2.5) == []


def test_bench_diff_committed_snapshots_self_consistent():
    """Both committed snapshots must diff clean against themselves via
    the real git plumbing (guards the `git show` path)."""
    for name in bench_diff.DEFAULT_FILES:
        if bench_diff.committed_json(ROOT, name, "HEAD") is None:
            continue  # snapshot not committed yet at this ref
        with open(os.path.join(ROOT, name)) as fh:
            fresh = json.load(fh)
        committed = bench_diff.committed_json(ROOT, name, "HEAD")
        if json.dumps(fresh, sort_keys=True) == json.dumps(
            committed, sort_keys=True
        ):
            assert bench_diff.diff_file(ROOT, name, "HEAD", band=2.5) == []


# ----------------------------------------------------------------------
# trace tooling
# ----------------------------------------------------------------------
def test_bench_diff_cli_skips_trace_sidecars(tmp_path):
    """A *.trace.json sidecar is never diffed — not even when named
    explicitly, and not even when it doesn't exist."""
    root = _git_repo_with_baseline(tmp_path, BASELINE)
    res = subprocess.run(
        [
            sys.executable, os.path.join(ROOT, "tools", "bench_diff.py"),
            "--root", root,
            "--files", "BENCH.json", "BENCH.trace.json",
        ],
        capture_output=True, text=True,
    )
    assert res.returncode == 0, res.stderr
    assert "BENCH.trace.json: trace sidecar, skipped" in res.stdout
    assert "checked 1 files" in res.stdout


def test_trace_check_passes_on_repo():
    """tools/trace_check.py builds a small traced run end to end and
    validates the Perfetto export (the `make trace-check` gate)."""
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    res = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "trace_check.py")],
        capture_output=True, text=True, env=env, cwd=ROOT,
    )
    assert res.returncode == 0, f"{res.stdout}\n{res.stderr}"
    assert "0 problems" in res.stdout


def test_trace_report_summarizes_a_trace(tmp_path):
    """trace_report renders per-phase stats, straggler lanes, and the
    embedded metrics from a written trace file."""
    import numpy as np

    from repro import obs
    from repro.core.constructions import PlanConfig
    from repro.core.planner import BlockShapes, get_plan_for
    from repro.runtime import run_over_pool
    from repro.runtime.pool import sample_trace

    obs.TRACER.clear()
    obs.enable()
    try:
        cfg = PlanConfig("age", 2, 2, 2).resolved()
        plan = get_plan_for(cfg, BlockShapes(k=4, ma=4, mb=4, s=2, t=2))
        rng = np.random.default_rng(0)
        a = rng.integers(0, 7, (4, 4))
        b = rng.integers(0, 7, (4, 4))
        run_over_pool(plan, a, b, sample_trace(plan.n_total, seed=1), seed=0)
        path = str(tmp_path / "trace.json")
        obs.write_chrome(path, obs.TRACER, metrics=obs.snapshot())
    finally:
        obs.disable()
        obs.TRACER.clear()
    res = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "trace_report.py"), path],
        capture_output=True, text=True,
    )
    assert res.returncode == 0, res.stderr
    assert "phase2.compute" in res.stdout
    assert "straggler attribution" in res.stdout
    assert "subset_cache" in res.stdout
    assert "wire bytes" in res.stdout


def test_trace_report_missing_file_fails_loudly(tmp_path):
    res = subprocess.run(
        [
            sys.executable, os.path.join(ROOT, "tools", "trace_report.py"),
            str(tmp_path / "absent.trace.json"),
        ],
        capture_output=True, text=True,
    )
    assert res.returncode == 1
    assert "not found" in res.stderr
