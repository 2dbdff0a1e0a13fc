"""The SST constituency Tree-LSTM served through the async single-shot tier
at a small width (embed 12, hidden 8, vocab 50), against a plain reference
(``treelstm_ref.py``, a copy of ``benchmarks/chip/refs/treelstm.py``).

Fresh trees are packed on the loop and served on the bucketed tier once
their spec is built; run-uniform step widths keep the waves of the cell's
traffic on the few specs that set-up builds, and leave the lm feed round's
specs as they were."""

import json
import os
import sys

import numpy as np
import pytest

import treelstm_ref as ref

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks", "chip")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


def _load(kind: str, name: str) -> dict:
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


CFG = dict(_load("configs", "treelstm-sst-const"), embed=12, hidden=8,
           vocab=50)
TRAFFIC = _load("traffic", "classify-sat")
CLIENTS = TRAFFIC["clients"]


def _family():
    import harness

    return harness.load_module("families", "tree")


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """The workload at the small width and a registry holding its trained
    tree policy."""
    from repro.launch.serve import train_policies
    from repro.serve import PolicyRegistry

    wl = _family().make_workload(CFG, 0)
    reg = PolicyRegistry(str(tmp_path_factory.mktemp("registry")))
    train_policies(reg, ["tree"], {"tree": wl}, 0)
    return wl, reg


@pytest.fixture(scope="module")
def served(trained):
    """An engine with the trained tree policy and async compile whose
    specs were prewarmed from the cell's prewarm waves, builds drained."""
    from repro.serve import ServeEngine

    fam = _family()
    wl, reg = trained
    eng = ServeEngine({"tree": wl}, registry=reg, async_compile=True,
                      compile_workers=2, model_size=CFG["hidden"])
    warm = fam.Traffic(TRAFFIC["lengths"], CFG["vocab"], 3000000019, 2)
    graphs = [warm.wave_graph(CLIENTS)
              for _ in range(TRAFFIC["prewarm_waves"])]
    graphs.append(warm.wave_graph(CLIENTS, TRAFFIC["prewarm_depth"]))
    n_jobs = eng.prewarm({"version": 1,
                          "families": {"tree": {"graphs": graphs}}})
    eng._compiler.drain()
    ex, pol = eng._executor("tree"), eng.policy_for("tree")
    specs = {ex.pack_ready(g, pol, layout="schedule").spec for g in graphs}
    yield eng, fam, specs, n_jobs
    eng.close()


def _wave(eng, fam, seed: int):
    """Submit one wave of the cell's traffic; returns (requests, trees)."""
    t = fam.Traffic(TRAFFIC["lengths"], CFG["vocab"], seed, 1)
    trees = [t.next_tree() for _ in range(CLIENTS)]
    return _submit(eng, trees), trees


def _submit(eng, trees):
    from repro.models.trees import tree_graph
    from repro.serve import graph_request

    reqs = [graph_request("tree", tree_graph([tr]), arrival=eng._now)
            for tr in trees]
    eng.submit_many(reqs)
    return reqs


def test_prewarm_builds_two_specs(served):
    eng, _, specs, n_jobs = served
    assert len(specs) == n_jobs == 2
    assert eng._compiler.stats["landed"] == 2
    # The drawn waves share one spec; the deep wave's internal run is 32
    # steps instead of 16.
    assert sorted(len(s.steps) for s in specs) == [19, 35]


def test_fresh_trees_bucketed_equal_reference(served):
    eng, fam, _, _ = served
    st = eng.stats
    floor0, fresh0 = st.n_single_floor, st.n_single_fresh_pack
    bucketed0 = st.tier_rounds.get("bucketed", 0)
    reqs, trees = _wave(eng, fam, 3000000023)
    eng.step()
    assert st.tier_rounds.get("bucketed", 0) == bucketed0 + 1
    assert st.n_single_floor == floor0
    assert st.n_single_fresh_pack == fresh0 + 1
    w = ref.weights(CFG, CFG["weight_seed"])
    gap = 0.0
    for req, tree in zip(reqs, trees):
        assert req.status == "COMPLETED"
        assert req.graph is None      # the ledger keeps results, not graphs
        want = ref.forward(w, fam.nested(tree))
        leaves, internal = fam.n_nodes(fam.nested(tree))
        assert internal == leaves - 1
        assert req.result.shape == want.shape == (2 * leaves - 1,
                                                  CFG["classes"])
        gap = max(gap, float(np.max(np.abs(req.result - want))))
    assert gap < 1e-5


def test_never_seen_wave_with_built_spec_submits_no_job(served):
    eng, fam, _, _ = served
    st = eng.stats
    sub0 = eng._compiler.stats["submitted"]
    fresh0 = st.n_single_fresh_pack
    real0, pad0 = st.n_single_lanes_real, st.n_single_lanes_padded
    reqs, trees = _wave(eng, fam, 3000000029)
    eng.step()
    assert all(r.status == "COMPLETED" for r in reqs)
    assert eng._compiler.stats["submitted"] == sub0
    assert st.n_single_fresh_pack == fresh0 + 1
    leaves = sum(len(fam.leaf_tokens(t)) for t in trees)
    nodes = sum(5 * len(fam.leaf_tokens(t)) - 2 for t in trees)
    assert st.n_single_lanes_real - real0 == nodes
    assert st.n_single_lanes_padded - pad0 > 0
    assert leaves == sum(TRAFFIC["lengths"])


def test_cell_waves_land_in_prewarmed_specs(served):
    """500 waves of the cell's traffic (seeds the prewarm did not use) pack
    to specs that set-up built. A spec does not depend on the rows' order,
    so the waves are lowered without the PQ-tree plan here, after checking
    that on a few."""
    from repro.core.plan import BucketedPlanExecutor

    eng, fam, specs, _ = served
    pol = eng.policy_for("tree")
    ex = eng._executor("tree")
    plain = BucketedPlanExecutor(ex.impls, None, layout="schedule",
                                 ladder=eng.bucket_ladder)
    t = fam.Traffic(TRAFFIC["lengths"], CFG["vocab"], 3000000031, 1)
    for i in range(500):
        g = t.wave_graph(CLIENTS)
        spec = plain.pack_for(g, pol).spec
        if i < 3:
            assert ex.pack_for(g, pol).spec == spec
        assert spec in specs, i


def test_small_fresh_wave_packs_on_the_loop_without_the_pq_plan(
        trained, monkeypatch):
    """A wave of 8 short trees has under 512 layout variables, where the
    executor's own layout would run the joint PQ-tree plan (seconds to
    minutes a wave). The loop packs it in declaration order, serves the
    floor while its spec builds, and serves the same trees bucketed once
    the spec is built; no thread runs the PQ-tree planner."""
    import random

    from repro.core import memplan
    from repro.models.data import random_tree
    from repro.serve import ServeEngine
    from repro.serve.scheduler import merge_request_graphs

    calls = []

    def refuse(name):
        def planner(*args, **kwargs):
            calls.append(name)
            raise RuntimeError(f"{name} ran")
        return planner

    monkeypatch.setattr(memplan, "plan_rows", refuse("plan_rows"))
    monkeypatch.setattr(memplan, "plan_rows_chunked",
                        refuse("plan_rows_chunked"))
    wl, reg = trained
    eng = ServeEngine({"tree": wl}, registry=reg, async_compile=True,
                      compile_workers=1, model_size=CFG["hidden"])
    try:
        rng = random.Random(3000000037)
        trees = [random_tree(rng, n, CFG["vocab"])
                 for n in (3, 4, 5, 6, 7, 8, 9, 9)]
        ex = eng._executor("tree")
        st = eng.stats
        reqs = _submit(eng, trees)
        wave, _ = merge_request_graphs(reqs)
        n_vars = sum(len(ex.impls[n.type].out_fields) for n in wave.nodes)
        assert ex.layout == "planned" and n_vars <= ex.max_pq_vars
        eng.step()
        assert all(r.status == "COMPLETED" for r in reqs)
        assert st.tier_rounds.get("interpreted", 0) == 1
        assert st.n_single_fresh_pack == st.n_single_floor == 1
        assert eng._compiler.stats["submitted"] == 1
        pack = ex.pack_ready(wave, eng.policy_for("tree"),
                             layout="schedule")
        assert pack.stats.layout == "schedule"
        eng._compiler.drain()
        again = _submit(eng, trees)
        eng.step()
        assert st.tier_rounds.get("bucketed", 0) == 1
        assert st.n_single_fresh_pack == 1
        for a, b in zip(reqs, again):
            np.testing.assert_allclose(b.result, a.result, atol=1e-5)
    finally:
        eng.close()
    assert calls == []


def test_deep_tree_has_the_asked_height():
    fam = _family()
    toks = list(range(56))
    tree = fam.deep_tree(toks, 24)
    assert fam.height(tree) == 24 and fam.leaf_tokens(tree) == toks
    assert fam.height(fam.deep_tree(toks[:5], 24)) == 4


@pytest.fixture(scope="module")
def lm_engine():
    from repro.models.chains import ChainLM
    from repro.serve import ServeEngine

    return ServeEngine({"lm": ChainLM(16, 0, vocab=32)}, max_slots=64)


def _lm_expected(width: int):
    """The lm feed round's spec at a padded width, as the bucketed plan
    packed it before run-uniform widths (hidden 16, vocab 32)."""
    from repro.core.plan import BucketSpec, BucketStepSpec

    h, c, x, y = (("h_out", (16,)), ("c_out", (16,)), ("x", (16,)),
                  ("y", (32,)))
    steps = (
        BucketStepSpec("E", width, (), (("x", x),)),
        BucketStepSpec("R", width, (), (("h_out", h), ("c_out", c))),
        BucketStepSpec("C", width, (x, h, c), (("h_out", h), ("c_out", c))),
        BucketStepSpec("O", width, (h,), (("y", y),)))
    rows = ((c, 2 * width + 1), (h, 2 * width + 1), (x, width + 1),
            (y, width + 1))
    return BucketSpec(steps, rows)


@pytest.mark.parametrize("count", range(8, 65))
def test_lm_feed_round_spec_unchanged(lm_engine, count):
    from repro.serve.scheduler import RoundPlan, build_lm_feed_round_graph

    g, _ = build_lm_feed_round_graph(RoundPlan(), count=count)
    pack = lm_engine._executor("lm").pack_for(g, lm_engine.policy_for("lm"))
    assert pack.spec == _lm_expected(max(8, 1 << (count - 1).bit_length()))


def test_run_uniform_widths():
    """Every step of a same-type run takes the bucket of the run's widest
    step; run lengths still pad to a power of two."""
    from repro.core.plan import (LoweredOperand, LoweredStep, Lowering,
                                 PlanStats, pack_bucketed)

    key = ("h", (4,))
    steps, aux = [], []
    for j, (t, k) in enumerate([("A", 3), ("B", 20), ("B", 9), ("B", 2),
                                ("C", 5)]):
        ids = tuple(range(len(aux), len(aux) + k))
        steps.append(LoweredStep(
            type=t, ids=ids, k=k, aux_start=len(aux),
            inputs=() if t == "A" else (LoweredOperand(key, "slice", 0),),
            outputs=(("h", LoweredOperand(key, "slice", len(aux))),)))
        aux.extend(ids)
    low = Lowering(steps, np.asarray(aux, np.int32), {}, {key: len(aux)},
                   PlanStats(n_steps=5))
    pack = pack_bucketed(low, ladder=(8,))
    got = [(s.type, s.width) for s in pack.spec.steps]
    assert got == [("A", 8), ("B", 32), ("B", 32), ("B", 32), ("B", 32),
                   ("C", 8)]
    assert pack.stats.n_real_lanes == 39
    assert pack.spec.n_aux_lanes == 8 + 4 * 32 + 8
