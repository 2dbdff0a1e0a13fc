"""The lm round's commit inside its bucket program (core/plan.py
``CommitSpec``, serve/engine.py; DESIGN.md §9): the committing program's
tokens and slot pools are bit-identical to ``_fused_commit`` run over the
plain program's arenas, dummy lanes write no slot, the engine's bucketed
tier serves the same tokens as the interpreted floor with one dispatch and
one token read a round, and every other tier keeps its host commit."""

import json
import os
import random
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

import repro.serve.engine as engine
from repro.core.batching import SufficientConditionPolicy
from repro.core.executor import DynamicExecutor
from repro.core.plan import BucketedPlanExecutor, CommitSpec, _params_kind
from repro.models.workloads import make_workload
from repro.obs import Obs, Tracer
from repro.serve import ServeEngine, ServeStats, lm_request
from repro.serve.resilience import snapshot_engine
from repro.serve.scheduler import (DUMMY_SLOT, LMEntry, RoundPlan,
                                   build_lm_feed_round_graph)

MODEL_SIZE = 8
SLOTS = 16
POLICY = SufficientConditionPolicy()
SRC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")


@pytest.fixture(scope="module")
def lm():
    return make_workload("ChainLM", MODEL_SIZE)


def _spec(wl):
    return CommitSpec("y", tuple(wl.state_fields), "R", engine._commit_stage)


def _pool(wl, seed=0):
    rng = np.random.default_rng(seed)
    return {f: jnp.asarray(rng.standard_normal((SLOTS, MODEL_SIZE)),
                           jnp.float32) for f in wl.state_fields}


def _feed_graph(wl, n_live, count=None, seed=0):
    """A feed round of ``n_live`` decoding entries on distinct random
    slots, padded to its count bucket (or to ``count``)."""
    rng = np.random.default_rng(seed)
    plan = RoundPlan()
    for slot in rng.permutation(SLOTS)[:n_live]:
        req = lm_request([1, 2, 3], 4)
        req.out = [int(rng.integers(0, wl.vocab))]
        plan.decodes.append(LMEntry(req, int(slot)))
    return build_lm_feed_round_graph(plan, count=count)


def _host_commit(wl, res, entries, pool):
    """The reference: ``_fused_commit`` over a plain run's arenas."""
    fields = list(wl.state_fields)
    y_arena, y_rows = res.arena_rows("y", [e.o_node for e in entries])
    cells = [e.cell_node for e in entries]
    pairs = [res.arena_rows(f, cells) for f in fields]
    slots = np.asarray([e.slot for e in entries], np.int32)
    toks, pools = engine._fused_commit(
        y_arena, y_rows, slots, [a for a, _ in pairs], [r for _, r in pairs],
        [pool[f] for f in fields])
    return np.asarray(toks), dict(zip(fields, pools))


# -- the executor ---------------------------------------------------------------


@pytest.mark.parametrize("n_live,count", [
    (3, None),    # below the count bucket (8)
    (8, None),    # at it
    (11, None),   # between buckets: padded to 16
    (5, 16),      # a round padded to a coarser count, as the bridge pads
    (0, 8),       # all dummies: the graph _prewarm_lm builds
    (0, 16),
])
def test_in_program_commit_matches_fused_commit(lm, n_live, count):
    graph, entries = _feed_graph(lm, n_live, count)
    assert len(entries) == n_live
    pool = _pool(lm)
    params = {"slots": pool}
    plain = BucketedPlanExecutor(lm.impls, None, ladder=(8,))
    comm = BucketedPlanExecutor(lm.impls, None, ladder=(8,),
                                commit=_spec(lm))
    res = plain.run(graph, POLICY, params=params)
    handle = comm.dispatch_packed(graph, comm.pack_for(graph, POLICY),
                                  params=params)
    assert handle.in_program and handle.pending
    toks, new = handle.tokens()
    assert not handle.pending
    assert toks.shape == (len(graph) // 4,)      # a token for every lane
    held = {e.slot for e in entries}
    if entries:
        ref_toks, ref_pools = _host_commit(lm, res, entries, pool)
        np.testing.assert_array_equal(toks[:n_live], ref_toks)
        for f in lm.state_fields:
            np.testing.assert_array_equal(np.asarray(new[f]),
                                          np.asarray(ref_pools[f]))
    for f in lm.state_fields:
        for s in range(SLOTS):
            if s not in held:     # bit-unchanged, dummy lanes included
                np.testing.assert_array_equal(np.asarray(new[f][s]),
                                              np.asarray(pool[f][s]))
    # The block() view of the same run still reads the arenas.
    outs = [n.id for n in graph.nodes if n.type == "O"]
    np.testing.assert_array_equal(np.asarray(handle.block().field("y", outs)),
                                  np.asarray(res.field("y", outs)))


def test_dummy_fragments_read_past_every_slot(lm):
    graph, entries = _feed_graph(lm, 3)
    r_aux = [n.attrs["aux"] for n in graph.nodes if n.type == "R"]
    assert r_aux[:3] == [e.slot for e in entries]
    assert r_aux[3:] == [DUMMY_SLOT] * 5


def test_committing_and_plain_builds_never_share_an_executable(lm):
    graph, _ = _feed_graph(lm, 3)
    params = {"slots": _pool(lm)}
    exes = {}
    plain = BucketedPlanExecutor(lm.impls, None, ladder=(8,), exe_cache=exes,
                                 namespace="lm")
    comm = BucketedPlanExecutor(lm.impls, None, ladder=(8,), exe_cache=exes,
                                namespace="lm", commit=_spec(lm))
    pp, pc = plain.pack_for(graph, POLICY), comm.pack_for(graph, POLICY)
    assert pp.spec == pc.spec
    assert pp.commit_idx is None and pc.commit_idx is not None
    kp, kc = plain.executable_key(pp, params), comm.executable_key(pc, params)
    assert kp == ("lm", pp.spec, _params_kind(params))     # as before
    assert kc[:3] == kp and kc != kp
    plain.run(graph, POLICY, params=params)
    comm.run(graph, POLICY, params=params)
    assert plain.n_bucket_compiles == comm.n_bucket_compiles == 1


@pytest.mark.parametrize("family", ["tree", "lattice"])
def test_single_shot_programs_are_unchanged(family):
    """Tree and lattice executors commit nothing: the engine builds them
    plain, their executable key is the plain key, and their outputs equal
    a plain executor's bit for bit."""
    wl = make_workload({"tree": "TreeLSTM", "lattice": "LatticeLSTM"}[family],
                       MODEL_SIZE)
    eng = ServeEngine({family: wl}, compiled=True, bucketed=True)
    ex = eng._executor(family)
    assert ex.commit is None
    small = ({"leaves_lo": 3, "leaves_hi": 4} if family == "tree"
             else {"lo": 3, "hi": 4})
    graph = wl.sample_graph(random.Random(3), 1, **small)
    pack = ex.pack_for(graph, POLICY)
    assert ex.executable_key(pack, None) == (ex._ns, pack.spec,
                                             _params_kind(None))
    got = ex.run(graph, POLICY)
    ref = BucketedPlanExecutor(wl.impls, None, ladder=(8,)).run(graph,
                                                                POLICY)
    floor = DynamicExecutor(wl.impls, None).run(graph, POLICY)
    outs = [n.id for n in graph.nodes if n.type == "O"]
    fld = next(iter(wl.impls["O"].out_fields))
    np.testing.assert_array_equal(np.asarray(got.field(fld, outs)),
                                  np.asarray(ref.field(fld, outs)))
    np.testing.assert_allclose(np.asarray(got.field(fld, outs)),
                               np.asarray(floor.field(fld, outs)),
                               rtol=1e-5, atol=1e-6)


# -- the engine -----------------------------------------------------------------


def _staggered(seed=0, n=14):
    """Refills in groups (arrivals shared by 2-4 requests) and completions
    spread over the rounds, so speculation both promotes and bails."""
    rng = np.random.default_rng(seed)
    reqs, t = [], 0.0
    while len(reqs) < n:
        for _ in range(int(rng.integers(2, 5))):
            prompt = [int(x) for x in rng.integers(0, 256,
                                                   int(rng.integers(2, 9)))]
            reqs.append(lm_request(prompt, int(rng.integers(2, 9)),
                                   arrival=t))
        t += float(rng.integers(1, 6))
    return reqs[:n]


def _serve(lm, reqs, **kw):
    kw.setdefault("compiled", True)
    kw.setdefault("bucketed", True)
    eng = ServeEngine({"lm": lm}, continuous=True, max_slots=6, **kw)
    eng.submit_many(reqs)
    stats = eng.run()
    eng.close()
    return eng, stats


def _tokens(reqs):
    return [list(r.out) for r in reqs]


@pytest.fixture(scope="module")
def floor_tokens(lm):
    reqs = _staggered()
    _serve(lm, reqs, compiled=False, bucketed=False)
    return _tokens(reqs)


@pytest.mark.parametrize("pipeline,donate", [(True, False), (False, False),
                                             (True, True)])
def test_bucketed_engine_serves_the_floors_tokens(lm, floor_tokens, pipeline,
                                                  donate):
    reqs = _staggered()
    eng, stats = _serve(lm, reqs, pipeline=pipeline, donate=donate)
    assert _tokens(reqs) == floor_tokens
    rounds = stats.tier_rounds.get("bucketed", 0)
    assert rounds == stats.n_rounds > 0
    assert stats.n_commit_in_program == rounds
    assert (eng.metrics.snapshot()["counters"]["serve.lm.commit_in_program"]
            >= rounds)
    if pipeline:
        assert stats.n_overlapped_packs > 0
    # Checkpointed with the rest of the stats.
    doc = snapshot_engine(eng)["stats"]["engine"]
    assert doc["n_commit_in_program"] == stats.n_commit_in_program


def test_floor_commits_on_the_host(lm, floor_tokens):
    reqs = _staggered()
    _, stats = _serve(lm, reqs, compiled=False, bucketed=False)
    assert stats.n_commit_in_program == 0
    assert _tokens(reqs) == floor_tokens


def test_coarse_bridge_commits_on_the_host(lm):
    """While the native count-8 build is in flight, rounds ride the
    count-16 program through the coarse bridge and commit on the host;
    once the native build lands they commit in the program."""
    sync_reqs = _staggered(seed=4, n=6)
    _serve(lm, sync_reqs)
    eng = ServeEngine({"lm": lm}, compiled=True, bucketed=True,
                      continuous=True, max_slots=6, async_compile=True)
    assert eng.prewarm({"families": {"lm": {"counts": [16]}}}) == 1
    assert eng._compiler.drain(timeout_s=60.0)
    reqs = _staggered(seed=4, n=6)
    eng.submit_many(reqs)
    stats = eng.run()
    eng.close()
    assert stats.tier_rounds.get("coarse", 0) >= 1
    assert stats.n_commit_in_program == stats.tier_rounds.get("bucketed", 0)
    assert _tokens(reqs) == _tokens(sync_reqs)


def test_prewarmed_dummy_round_leaves_every_slot(lm):
    eng = ServeEngine({"lm": lm}, compiled=True, bucketed=True,
                      max_slots=SLOTS, async_compile=True)
    assert eng.prewarm({"families": {"lm": {"counts": [8]}}}) == 1
    assert eng._compiler.drain(timeout_s=60.0)
    eng.close()
    pool = eng._lm_pool()
    pool.update(_pool(lm, seed=2))
    before = {f: np.asarray(v) for f, v in pool.items()}
    graph, _ = build_lm_feed_round_graph(RoundPlan(), count=8)
    ex = eng._executor("lm")
    pack = ex.pack_ready(graph, eng.policy_for("lm"))
    assert pack is not None and ex.executable_ready(pack, {"slots": pool})
    _, new = ex.dispatch_packed(graph, pack,
                                params={"slots": pool}).tokens()
    for f in lm.state_fields:
        np.testing.assert_array_equal(np.asarray(new[f]), before[f])


def test_token_altering_commit_reaches_the_bucketed_tier(lm, floor_tokens,
                                                         monkeypatch):
    """A fault in ``engine._fused_commit`` is served by the bucketed tier:
    the in-program stage looks the function up when the program is
    traced."""
    orig = engine._fused_commit

    def altered(y_arena, y_rows, slots, state_arenas, state_rows, pools):
        toks, new = orig(y_arena, y_rows, slots, state_arenas, state_rows,
                         pools)
        return (toks + 1) % y_arena.shape[-1], new

    monkeypatch.setattr(engine, "_fused_commit", altered)
    reqs = _staggered()
    _, stats = _serve(lm, reqs)
    assert stats.n_commit_in_program == stats.n_rounds > 0
    assert _tokens(reqs) != floor_tokens


def test_in_program_round_spans(lm):
    """``round.commit`` holds the host residue and is stamped
    ``in_program``; the token wait is a ``plan.block`` inside
    ``round.readback``; no round blocks on its arenas."""
    tr = Tracer(enabled=True)
    _, stats = _serve(lm, _staggered(n=6), obs=Obs(tracer=tr))
    tr.enabled = False
    spans = tr.spans()
    commits = [s for s in spans if s["name"] == "round.commit"]
    assert len(commits) == stats.n_commit_in_program > 0
    assert all(s["args"].get("in_program") for s in commits)
    assert not tr.spans("round.settle")
    blocks = tr.spans("plan.block")
    reads = tr.spans("round.readback")
    assert len(blocks) == len(reads) == stats.n_rounds
    for b, r in zip(blocks, reads):
        assert r["ts"] <= b["ts"] and b["ts"] + b["dur"] <= r["ts"] + r["dur"]


def test_commit_counter_merges():
    a, b = ServeStats(n_commit_in_program=3), ServeStats(n_commit_in_program=4)
    assert ServeStats.merged([a, b]).n_commit_in_program == 7


def test_sharded_engine_commits_on_the_host(tmp_path):
    """K = 2 replicas (forced host devices, so a process of its own): the
    shard_map program commits nothing, and the tokens are the floor's."""
    code = textwrap.dedent("""
        import json
        import numpy as np
        from repro.models.workloads import make_workload
        from repro.serve import ServeEngine, lm_request

        def trace():
            rng = np.random.default_rng(0)
            return [lm_request([int(x) for x in rng.integers(0, 256, 3 + i % 4)],
                               2 + i % 3, arrival=float(i // 2))
                    for i in range(8)]

        wl = make_workload("ChainLM", 8)
        out = {}
        for name, kw in (("sharded", dict(n_shards=2)),
                         ("floor", dict(compiled=False, bucketed=False))):
            reqs = trace()
            eng = ServeEngine({"lm": wl}, continuous=True, max_slots=4, **kw)
            eng.submit_many(reqs)
            st = eng.run()
            out[name] = {"tokens": [r.out for r in reqs],
                         "in_program": st.n_commit_in_program,
                         "tiers": st.tier_rounds}
        print(json.dumps(out))
    """)
    env = {**os.environ, "PYTHONPATH": SRC, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=2"}
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=str(tmp_path))
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["sharded"]["tiers"].get("sharded", 0) > 0
    assert got["sharded"]["in_program"] == 0
    assert got["floor"]["in_program"] == 0
    assert got["sharded"]["tokens"] == got["floor"]["tokens"]
