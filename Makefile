# Convenience targets; `pythonpath` in pyproject.toml makes the bare
# checkout importable, so no PYTHONPATH=src hack is needed.

PYTHON ?= python

.PHONY: test test-fast bench bench-json bench-edge bench-serve quickstart \
	docs-check bench-diff trace-check fuzz-kernels

test:
	$(PYTHON) -m pytest -q

test-fast:
	$(PYTHON) -m pytest -x -q

bench:
	PYTHONPATH=src $(PYTHON) -m benchmarks.run

# Machine-readable perf snapshot: refreshes BENCH_protocol.json at the
# repo root so later PRs can track regressions.
bench-json:
	PYTHONPATH=src $(PYTHON) -m benchmarks.protocol_batch

# PolyDot vs AGE over identical edge worker-pool traces; refreshes
# BENCH_edge.json at the repo root.  TRACE=1 additionally writes a
# Perfetto-loadable BENCH_edge.trace.json sidecar (report unchanged).
bench-edge:
	PYTHONPATH=src TRACE=$(TRACE) $(PYTHON) -m benchmarks.edge_runtime

# Serving-engine load benchmark: continuous vs boundary batching under
# open-loop Poisson arrivals; refreshes BENCH_serve.json at the repo root.
bench-serve:
	PYTHONPATH=src $(PYTHON) -m benchmarks.serve_load

quickstart:
	PYTHONPATH=src $(PYTHON) examples/quickstart.py

# Verify every relative link in README.md and docs/*.md resolves.
docs-check:
	$(PYTHON) tools/check_doc_links.py

# Compare freshly regenerated BENCH_*.json against the committed
# snapshots (deterministic leaves exact, wall-clock within a band).
bench-diff:
	$(PYTHON) tools/bench_diff.py

# Differential fuzz of the GF(p) matmul backends (f32limb / int32 /
# both Pallas kernels in interpret mode / CRT) against the
# arbitrary-precision host oracle.  Fixed seed = reproducible CI gate;
# raise FUZZ_EXAMPLES locally for a longer hunt.
FUZZ_EXAMPLES ?= 24
FUZZ_SEED ?= 0
fuzz-kernels:
	$(PYTHON) tools/fuzz_kernels.py --examples $(FUZZ_EXAMPLES) --seed $(FUZZ_SEED) -q

# Generate a small trace end-to-end (replay + adaptive decision) and
# verify the Chrome/Perfetto export: schema-valid, all three protocol
# phases, per-worker scheduler events, >= 1 AutoPlanner decision.
trace-check:
	PYTHONPATH=src $(PYTHON) tools/trace_check.py
