"""The private routed-expert layer (``repro.serve.moe``) at a small size:
hidden 64, expert width 32, 16 routed experts, top 4 of 4 groups with 2
kept, 4 held, AGE s=2 t=2 z=2.  The plain reference is the benchmark's
(``bench/layers/routed_experts.py``), which imports nothing of the
program.  (``test_moe.py`` is the plaintext model's MoE.)"""
import dataclasses
import json
import os
import sys

import numpy as np
import pytest

from repro.core.constructions import PlanConfig
from repro.core.gf import Field
from repro.runtime import ShiftedExponential, sample_trace
from repro.serve import DONE, ServingEngine
from repro.serve.moe import MoEConfig, PrivateMoE, route, swiglu_scaled

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import layers  # noqa: E402
from bench.workload import Activations  # noqa: E402

LAYER = layers.load("routed_experts")
CONFIG = os.path.join(ROOT, "bench", "configs", "deepseek-v3-moe.age-s2t2z2.json")
SEED = 2 ** 33 + 5
P = 65521


def _small(held=(4, 5, 6, 7)) -> dict:
    with open(CONFIG) as f:
        d = json.load(f)
    d.update(
        hidden_size=64, moe_intermediate_size=32, n_routed_experts=len(held),
        num_experts_per_tok=4, n_group=4, topk_group=2, max_batch=2,
        published={"num_hidden_layers": 61, "n_routed_experts": 16},
        deployment=dict(d["deployment"], held_experts=list(held)),
        engine={"row_buckets": [16, 32]},
        pool=dict(d["pool"], traces=3),
        data=dict(d["data"], router_nonzeros=8),
    )
    return d


DEP = LAYER.from_dict(_small())
# every expert's matrices: an expert's seed stream is its own index's
WEIGHTS = LAYER.make_weights(dataclasses.replace(DEP, held=tuple(range(16))), SEED)


def _moe(dep=DEP, weights=WEIGHTS, engine=None):
    cfg = PlanConfig(dep.method, dep.s, dep.t, dep.z)
    traces = [
        sample_trace(cfg.n_workers + dep.n_spare, ShiftedExponential(0.1, 0.5),
                     seed=100 + i, net_scale=0.3)
        for i in range(3)
    ]
    return PrivateMoE(
        weights, dep.held,
        MoEConfig(dep.n_experts, dep.top_k, dep.n_group, dep.topk_group, dep.scaling),
        traces, cfg, bias=weights["bias"], row_buckets=dep.row_buckets,
        max_batch=dep.max_batch, field=Field(P),
        **dict(dict(admission=False, validate=True, seed=3), **(engine or {})),
    )


def _xs(n=5, rows=16):
    acts = Activations(SEED, rows, DEP.hidden)
    return [acts.next() for _ in range(n)]


def test_private_moe_equals_the_plain_reference_bitwise():
    moe = _moe()
    xs = _xs()
    reqs = [moe.submit(x) for x in xs]
    moe.run()
    assert all(r.state == DONE and r.replay >= 0 for r in reqs)
    for r, want in zip(reqs, LAYER.reference(DEP, WEIGHTS, xs)):
        assert r.y.shape == (16, DEP.hidden)
        np.testing.assert_array_equal(r.y, want)
    # and the routed part is there: some held expert served some row
    assert not np.array_equal(reqs[0].y, LAYER.reference(
        dataclasses.replace(DEP, held=()), WEIGHTS, xs[:1])[0])


def test_routing_ties_go_to_the_lower_index():
    cfg = MoEConfig(16, 4, 4, 2, 2.5)
    logits = np.zeros((3, 16))
    idx, w, scores = route(logits, np.zeros(16), cfg)
    # every score ties: groups 0 and 1 win, and their experts 0..3
    assert idx.tolist() == [[0, 1, 2, 3]] * 3
    np.testing.assert_array_equal(w, np.full((3, 4), 2.5 / 4))
    # three groups tie on their top-2 sums for two places: 0 and 2 win
    logits[0, [1, 2, 9, 10, 13, 14]] = 3.0
    idx, _, _ = route(logits, np.zeros(16), cfg)
    assert idx[0].tolist() == [1, 2, 9, 10]
    # groups 2 and 1 win; experts 4, 9, 10 tie above the 1/2s, then 5
    logits[1, [4, 9, 10]] = 3.0
    logits[1, 13] = 2.0
    idx, _, _ = route(logits, np.zeros(16), cfg)
    assert idx[1].tolist() == [4, 9, 10, 5]
    # the whole layer breaks ties the same way as the reference: a router
    # of 16 equal columns and no bias tie every expert of a token
    tied = np.repeat(WEIGHTS["router"][:, :1], 16, axis=1)
    weights = dict(WEIGHTS, router=tied, bias=np.zeros(16))
    dep = dataclasses.replace(DEP, held=(0, 1, 2, 3))
    moe = _moe(dep, weights)
    xs = _xs(2)
    reqs = [moe.submit(x) for x in xs]
    moe.run()
    for r, want in zip(reqs, LAYER.reference(dep, weights, xs)):
        np.testing.assert_array_equal(r.y, want)


def test_shares_add_up_to_the_uncut_layer():
    """Four chips' shares of 16 experts, the shared expert counted once,
    give the layer with all 16 held.  Tolerance: the sums run in another
    order, so a few float64 ulps of the result's magnitude."""
    xs = _xs(4)
    shares = []
    for first in range(0, 16, 4):
        held = tuple(range(first, first + 4))
        moe = _moe(dataclasses.replace(DEP, held=held))
        reqs = [moe.submit(x) for x in xs]
        moe.run()
        shares.append([r.y for r in reqs])
    uncut = LAYER.reference(dataclasses.replace(DEP, held=tuple(range(16))), WEIGHTS, xs)
    shared = LAYER.reference(dataclasses.replace(DEP, held=()), WEIGHTS, xs)
    for i, want in enumerate(uncut):
        got = sum(share[i] for share in shares) - 3 * shared[i]
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-12 * np.abs(want).max())


def test_every_stage_stays_inside_the_no_wrap_bound(monkeypatch):
    """k * max|Xq| * max|Wq| < (p-1)/2 for every private product of a
    step on the configuration's seeded data (small), and at the
    published widths for the data's ranges."""
    seen = []
    real_submit = ServingEngine.submit

    def submit(self, x, arrival, deadline=None, weight=None):
        req = real_submit(self, x, arrival, deadline, weight)
        w = self.weights[req.weight]
        xq, wq = np.rint(req.x * req.scale), np.rint(w * req.scale)
        seen.append((req.weight, w.shape[0] * np.abs(xq).max() * np.abs(wq).max()))
        return req

    monkeypatch.setattr(ServingEngine, "submit", submit)
    moe = _moe()
    for x in _xs(4):
        moe.submit(x)
    moe.run()
    names = {name for name, _ in seen}
    assert {"router", "shared.gate", "shared.up", "shared.down"} <= names
    assert any(n.startswith("expert.") and n.endswith(".down") for n in names)
    assert all(0 < bound < (P - 1) // 2 for _, bound in seen)

    from repro.core.layers import choose_scales

    with open(CONFIG) as f:
        full = LAYER.from_dict(json.load(f))
    # activations and weights below 1 in magnitude; h' below 1/2 (SwiGLU's scaling)
    for k, a_max, w_max in ((full.hidden, 1.0, 1.0), (full.width, 0.5, 1.0)):
        s = choose_scales(k, a_max, w_max, P)
        assert k * np.rint(a_max * s) * np.rint(w_max * s) < (P - 1) // 2


def test_swiglu_scaling_is_exact_and_bounded():
    rng = np.random.default_rng(0)
    g, u = rng.normal(size=(5, 32)) * 40, rng.normal(size=(5, 32)) * 40
    g[2] = u[2] = 0.0
    h, j = swiglu_scaled(g, u)
    top = np.abs(h).max(axis=1)
    assert np.all((top[[0, 1, 3, 4]] >= 0.25) & (top[[0, 1, 3, 4]] < 0.5)) and top[2] == 0
    np.testing.assert_array_equal(np.ldexp(h, j[:, None]), g / (1.0 + np.exp(-g)) * u)


def test_engine_serves_two_weights_and_three_row_counts_on_one_pool():
    rng = np.random.default_rng(1)
    cfg = PlanConfig("age", 2, 2, 1)
    traces = [sample_trace(cfg.n_workers + 2, ShiftedExponential(0.1, 0.5), seed=i,
                           net_scale=0.3) for i in range(4)]
    weights = {"a": rng.uniform(-1, 1, (16, 8)), "b": rng.uniform(-1, 1, (8, 12))}
    eng = ServingEngine(weights, traces, cfg, field=Field(P), seed=0, validate=True,
                        max_batch=4, row_buckets=(4, 8))
    reqs = []
    for rows in (2, 6, 8):
        for name, w in weights.items():
            x = rng.uniform(-1, 1, (rows, w.shape[0]))
            reqs.append((eng.submit(x, 0.0, weight=name), w))
    eng.run()
    field = Field(P)
    for r, w in reqs:
        assert r.state == DONE and r.y.shape == (r.x.shape[0], w.shape[1])
        want = field.decode(field.matmul(field.encode(r.x, r.scale),
                                         field.encode(w, r.scale)), r.scale ** 2)
        np.testing.assert_array_equal(r.y, want)
    # one session: rows 2 -> rung 4, rows 6 and 8 -> rung 8, per weight; a
    # replay of one request launches alone, of two pads nothing
    assert eng.rows_served == 2 * (2 + 6 + 8)
    assert eng.rows_launched == 2 * (4 + 2 * 8)
    assert {name for name, _ in eng._w_dev} == {"a", "b"}
    with pytest.raises(ValueError, match="name the weight"):
        eng.submit(np.zeros((2, 16)), 0.0)
    with pytest.raises(ValueError, match="outside the row buckets"):
        eng.submit(np.zeros((10, 16)), 0.0, weight="a")
    with pytest.raises(ValueError, match="multiples of t=2"):
        ServingEngine(weights, traces, cfg, row_buckets=(4, 6, 7))


def test_engine_pads_a_partial_batch_to_the_ladder():
    """A replay's batch pads with zero entries to the smallest batch the
    engine has launched for its shape class, and its rows to the rung;
    without ``row_buckets`` the first request's rows are the one rung."""
    rng = np.random.default_rng(2)
    cfg = PlanConfig("age", 2, 2, 1)
    traces = [sample_trace(cfg.n_workers + 2, ShiftedExponential(0.1, 0.5), seed=i,
                           net_scale=0.3) for i in range(2)]
    w = rng.uniform(-1, 1, (16, 8))
    eng = ServingEngine(w, traces, cfg, field=Field(P), seed=0, validate=True, max_batch=8)
    reqs = [eng.submit(rng.uniform(-1, 1, (4, 16)), 0.0) for _ in range(4)]
    assert eng.row_buckets == (4,) and eng.launch_batch(3, (16, 8, 4)) == 3
    eng.run()
    assert [eng.launch_batch(n, (16, 8, 4)) for n in range(1, 9)] == [4, 4, 4, 4, 5, 6, 7, 8]
    reqs += [eng.submit(rng.uniform(-1, 1, (4, 16)), 0.0) for _ in range(3)]
    reqs += [eng.submit(rng.uniform(-1, 1, (2, 16)), 1.0)]  # padded to the rung
    eng.run()
    assert eng.report().replays == 3 and eng.rows_launched == 3 * 4 * 4
    assert eng.rows_served == 7 * 4 + 2
    for r in reqs:
        assert r.state == DONE and r.y.shape == (r.x.shape[0], 8)
    # max_rows: a replay holds no more rows than it, and one request at least
    eng = ServingEngine(w, traces, cfg, field=Field(P), seed=0, validate=True,
                        max_batch=8, row_buckets=(4, 16), max_rows=32)
    assert (eng.batch_cap(4), eng.batch_cap(16)) == (8, 2)
    reqs = [eng.submit(rng.uniform(-1, 1, (12, 16)), 0.0) for _ in range(3)]
    eng.run()
    assert eng.report().replays == 2 and eng.rows_launched == 16 * (2 + 2)
    assert all(r.state == DONE and r.y.shape == (12, 8) for r in reqs)
    assert ServingEngine(w, traces, cfg, row_buckets=(4, 16), max_rows=8).batch_cap(16) == 1


def test_engine_refuses_a_request_no_scale_can_hold():
    """Where even scale 1 breaks k * max|x| * max|w| < (p-1)/2 the
    product could wrap mod p: the request is refused, naming k and the
    maxima, instead of served at scale 1."""
    cfg = PlanConfig("age", 2, 2, 1)
    trace = sample_trace(cfg.n_workers + 2, ShiftedExponential(0.1, 0.5), seed=0)
    w = np.ones((16, 8))
    eng = ServingEngine(w, [trace], cfg, field=Field(P))
    with pytest.raises(ValueError, match=r"k=16 \* max\|x\|=3000.* max\|w\|=1.0"):
        eng.submit(np.full((2, 16), 3000.0), 0.0)
    eng.submit(np.full((2, 16), 1000.0), 0.0)  # 16 * 1000 * 1 fits


def test_warm_compiles_every_shape_a_step_launches(monkeypatch):
    """After ``warm()`` a step launches only shapes it warmed."""
    import repro.serve.engine as engine_mod

    moe = _moe(engine={"validate": False})
    launched = []
    real_stack = engine_mod._stack

    def stack(ws):
        out = real_stack(ws)
        launched.append(tuple(out.shape))
        return out

    monkeypatch.setattr(engine_mod, "_stack", stack)
    assert moe.warm() == len(moe.launch_shapes())
    warmed = set(launched)
    assert {(b, k, out) for k, out, _, b in moe.launch_shapes()} == warmed
    launched.clear()
    for x in _xs(6):
        moe.submit(x)
    moe.run()
    assert launched and set(launched) <= warmed
