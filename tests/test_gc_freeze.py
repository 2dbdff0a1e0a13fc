"""The engine's warm-heap freeze (DESIGN.md §6): an async engine whose
bucket programs have landed, with no build pending or in flight, runs one
``gc.collect(); gc.freeze()`` at its next round boundary, once, and
``close()`` gives the permanent generation back. Served at the small SST
Tree-LSTM width of ``test_tree_sst.py`` (embed 12, hidden 8, vocab 50),
against the plain reference (``treelstm_ref.py``).

The permanent generation is process-wide, so every test unfreezes in
teardown: no other test of the worker runs on a frozen heap."""

import gc
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
FURTHER_WAVES = 3


@pytest.fixture(autouse=True)
def _unfreeze():
    yield
    gc.unfreeze()


def _family():
    import harness

    return harness.load_module("families", "tree")


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    from repro.launch.serve import train_policies
    from repro.serve import PolicyRegistry

    wl = _family().make_workload(CFG, 0)
    reg = PolicyRegistry(str(tmp_path_factory.mktemp("registry")))
    train_policies(reg, ["tree"], {"tree": wl}, 0)
    return wl, reg


def _engine(trained, **kw):
    """An engine under its own tracer and metrics registry, so the spans
    and counters read here are this engine's alone."""
    from repro.obs import MetricsRegistry, Obs, Tracer
    from repro.serve import ServeEngine

    wl, reg = trained
    obs = Obs(tracer=Tracer(enabled=True), metrics=MetricsRegistry())
    return ServeEngine({"tree": wl}, registry=reg, obs=obs,
                       model_size=CFG["hidden"], **kw)


def _prewarmed(trained, n_graphs: int):
    """An async engine with ``n_graphs`` of the cell's prewarm waves built
    and its builds drained, as the benchmark's set-up leaves it."""
    fam = _family()
    eng = _engine(trained, async_compile=True, compile_workers=2)
    warm = fam.Traffic(TRAFFIC["lengths"], CFG["vocab"], 3000000019, 2)
    graphs = [warm.wave_graph(CLIENTS) for _ in range(n_graphs)]
    assert eng.prewarm({"version": 1,
                        "families": {"tree": {"graphs": graphs}}}) >= 1
    eng._compiler.drain()
    return eng


def _wave(eng, seed: int):
    """Submit one wave of the cell's traffic; returns (requests, trees)."""
    from repro.models.trees import tree_graph
    from repro.serve import graph_request

    t = _family().Traffic(TRAFFIC["lengths"], CFG["vocab"], seed, 1)
    trees = [t.next_tree() for _ in range(CLIENTS)]
    reqs = [graph_request("tree", tree_graph([tr]), arrival=eng._now)
            for tr in trees]
    eng.submit_many(reqs)
    return reqs, trees


def _heap_frozen() -> bool:
    """Whether the tracked heap sits in the permanent generation. CPython
    3.12 moves immortal objects there at every collection, so a few
    hundred are found there with no freeze; a freeze moves every tracked
    object, and leaves far fewer outside than in."""
    return gc.get_freeze_count() > len(gc.get_objects())


def _freezes(eng) -> int:
    return eng.metrics.counter("serve.gc.freezes").snapshot()


@pytest.fixture(scope="module")
def served(trained):
    """A prewarmed, drained engine: what its first step left, and the
    waves it served over that step and ``FURTHER_WAVES`` more. The first
    step's readings are taken here, before any test's teardown
    unfreezes."""
    eng = _prewarmed(trained, 1)
    reqs, trees = _wave(eng, 3000000023)
    eng.step()
    first = {"freezes": _freezes(eng), "frozen": _heap_frozen(),
             "spans": eng.tracer.spans("serve.gc_freeze"),
             "gauge": eng.metrics.gauge("serve.gc.frozen_objects").value}
    waves = [(reqs, trees)]
    for i in range(FURTHER_WAVES):
        waves.append(_wave(eng, 3000000041 + i))
        eng.step()
    yield eng, first, waves
    eng.close()


def test_first_warm_step_freezes_once(served):
    _, first, _ = served
    assert first["freezes"] == 1
    assert first["frozen"]
    [span] = first["spans"]
    assert span["args"]["frozen"] == first["gauge"] > 0
    assert span["args"]["collect_ms"] >= 0


def test_no_second_freeze_over_further_rounds(served):
    eng, _, _ = served
    assert eng.stats.n_rounds == 1 + FURTHER_WAVES
    assert _freezes(eng) == 1
    assert len(eng.tracer.spans("serve.gc_freeze")) == 1


def test_frozen_engine_serves_the_reference(served):
    eng, _, waves = served
    fam = _family()
    assert eng.stats.tier_rounds.get("bucketed", 0) == 1 + FURTHER_WAVES
    w = ref.weights(CFG, CFG["weight_seed"])
    gap = 0.0
    for reqs, trees in waves:
        for req, tree in zip(reqs, trees):
            assert req.status == "COMPLETED"
            want = ref.forward(w, fam.nested(tree))
            assert req.result.shape == want.shape
            gap = max(gap, float(np.max(np.abs(req.result - want))))
    assert gap < 1e-5


def test_close_unfreezes(trained):
    eng = _prewarmed(trained, 1)
    try:
        _wave(eng, 3000000047)
        eng.step()
        assert _freezes(eng) == 1 and _heap_frozen()
    finally:
        eng.close()
    assert gc.get_freeze_count() == 0
    eng.close()                       # idempotent
    assert not _heap_frozen() and _freezes(eng) == 1


def test_no_freeze_before_a_build_lands_or_while_one_is_pending(
        trained, monkeypatch):
    """An async engine with nothing landed leaves the heap alone; one
    whose build is pending waits for it, and freezes at the first round
    boundary after."""
    eng = _engine(trained, async_compile=True, compile_workers=1)
    try:
        eng.step()
        assert _freezes(eng) == 0 and not _heap_frozen()
    finally:
        eng.close()
    eng = _prewarmed(trained, 1)
    try:
        monkeypatch.setattr(eng._compiler, "pending_count", lambda: 1)
        eng.step()
        assert _freezes(eng) == 0 and not _heap_frozen()
        monkeypatch.undo()
        eng.step()
        assert _freezes(eng) == 1 and _heap_frozen()
    finally:
        eng.close()


def test_sync_engine_never_freezes(trained):
    """Without the async service the engine compiles in the round and
    never freezes, however many rounds it serves."""
    eng = _engine(trained, async_compile=False)
    try:
        for i in range(2):
            reqs, _ = _wave(eng, 3000000053 + i)
            eng.step()
            assert all(r.status == "COMPLETED" for r in reqs)
        assert _freezes(eng) == 0
        assert not _heap_frozen()
        assert eng.tracer.spans("serve.gc_freeze") == []
    finally:
        eng.close()
