"""Durable elastic serving (serve/checkpoint.py, serve/resilience.py,
DESIGN.md §7): snapshot codecs, checkpoint integrity gating, admission
dedupe across restore, quarantine booking survival (backoff expiry and the
permanent cap measured in virtual rounds), kill/restore output
equivalence, replica-loss evacuation, parked-entry resume, and work
stealing. Every fault here is deterministic, so so are the assertions.

Multi-device tests skip unless jax sees >= 2 devices — run under
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (the CI chaos-smoke
job does; the codec/checkpoint/quarantine tests always run).
"""

import json
import math
import random

import jax
import numpy as np
import pytest

from repro.launch.jaxcache import (QUARANTINE_SUBDIR, audit_cache_dir,
                                   cache_dir, enable_compilation_cache)
from repro.models.workloads import make_workload
from repro.obs import FlightRecorder, MetricsRegistry, Obs, Tracer
from repro.serve import (InjectedCrash, ServeEngine, graph_request,
                         latest_checkpoint, lm_request, reserve_rids,
                         synth_trace)
from repro.serve.checkpoint import (CheckpointError, checkpoint_path,
                                    decode_array, decode_graph,
                                    decode_request, encode_array,
                                    encode_graph, encode_request,
                                    list_checkpoints, read_checkpoint,
                                    write_checkpoint)
from repro.serve.faults import FaultInjector, Quarantine, poison_requests
from repro.serve.queue import COMPLETED, FAILED, AdmissionQueue
from repro.serve.resilience import restore_engine, snapshot_engine

MODEL_SIZE = 8
FAMILIES = ["lm", "tree", "lattice"]

needs_devices = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs >= 2 devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=4)")


@pytest.fixture(scope="module")
def workloads():
    return {"lm": make_workload("ChainLM", MODEL_SIZE),
            "tree": make_workload("TreeLSTM", MODEL_SIZE),
            "lattice": make_workload("LatticeLSTM", MODEL_SIZE)}


def _trace(workloads, n=8, rate=3.0, max_new=3, seed=0):
    reqs = synth_trace(FAMILIES, n, rate, max_new, workloads, seed)
    for r in reqs:
        r.deadline = r.arrival + 500.0
    return reqs


def _ledger(eng):
    """rid-sorted request ledger: two runs of one trace draw different rids
    from the process-wide counter, so equivalence compares position-aligned
    sorted ledgers, never rid values."""
    return [eng.requests[rid] for rid in sorted(eng.requests)]


def _assert_equivalent(led, clean_led):
    assert len(led) == len(clean_led)
    for a, b in zip(led, clean_led):
        assert a.status == b.status
        if a.status != COMPLETED:
            continue
        if a.family == "lm":
            assert a.out == b.out
        else:
            assert np.array_equal(a.result, b.result)


# -- primitive codecs ---------------------------------------------------------


def test_array_codec_bit_exact():
    rng = np.random.default_rng(0)
    for a in (rng.standard_normal((3, 4)).astype(np.float32),
              np.array([-0.0, np.inf, -np.inf, np.float32(1e-40)],
                       np.float32),
              rng.integers(0, 1000, (5,), dtype=np.int32)):
        b = decode_array(encode_array(a))
        assert b.dtype == a.dtype and b.shape == a.shape
        assert a.tobytes() == b.tobytes()


def test_graph_codec_roundtrip(workloads):
    g = workloads["tree"].sample_graph(random.Random(0), 1,
                                       leaves_lo=3, leaves_hi=5)
    h = decode_graph(encode_graph(g))
    assert len(h) == len(g)
    for n, m in zip(g.nodes, h.nodes):
        assert (n.type, n.inputs, n.op) == (m.type, m.inputs, m.op)
        assert dict(n.attrs or {}) == dict(m.attrs or {})
    assert h.topology_key() == g.topology_key()


def test_request_codec_roundtrips_midflight_state():
    req = lm_request([3, 1, 4], 4, arrival=2.0)
    req.status = "RUNNING"
    req.out = [7, 7]
    req.feed = [0, 3, 1, 4]
    req.n_fed = 3
    req.park = {"h": np.arange(8, dtype=np.float32),
                "c": -np.ones(8, np.float32)}
    back = decode_request(encode_request(req))
    assert (back.rid, back.family, back.prompt) == (req.rid, "lm", [3, 1, 4])
    assert back.out == [7, 7] and back.feed == req.feed and back.n_fed == 3
    assert set(back.park) == {"h", "c"}
    assert np.array_equal(back.park["h"], req.park["h"])


def test_failed_poison_request_decodes_without_revalidation(workloads):
    bad = poison_requests(1, arrival=0.0)[0]
    bad.status = FAILED
    bad.error = {"code": "BAD_TOPOLOGY", "detail": "poisoned", "round": 0}
    back = decode_request(encode_request(bad))   # must not raise
    assert back.status == FAILED
    assert back.error["code"] == "BAD_TOPOLOGY"


# -- checkpoint document IO ---------------------------------------------------


def test_checkpoint_write_read_roundtrip(tmp_path):
    payload = {"clock": {"round": 3}, "x": [1, 2, 3]}
    p = str(tmp_path / "c.json")
    fp = write_checkpoint(p, payload)
    assert len(fp) == 64
    assert read_checkpoint(p) == payload
    assert not list(tmp_path.glob("*.tmp.*"))    # atomic: no temp residue


def test_checkpoint_rejects_tamper_version_and_truncation(tmp_path):
    p = str(tmp_path / "c.json")
    write_checkpoint(p, {"clock": {"round": 3}})
    doc = json.load(open(p))

    doc["payload"]["clock"]["round"] = 4         # bit-flip the state
    json.dump(doc, open(p, "w"))
    with pytest.raises(CheckpointError, match="fingerprint"):
        read_checkpoint(p)

    doc["version"] = 99                          # future schema
    json.dump(doc, open(p, "w"))
    with pytest.raises(CheckpointError, match="version"):
        read_checkpoint(p)

    open(p, "w").write('{"version": 1, "fing')   # torn write
    with pytest.raises(CheckpointError, match="unreadable"):
        read_checkpoint(p)


def test_checkpoint_listing_orders_by_round(tmp_path):
    for r in (12, 3, 7):
        write_checkpoint(checkpoint_path(str(tmp_path), r), {"r": r})
    cks = list_checkpoints(str(tmp_path))
    assert [r for r, _ in cks] == [3, 7, 12]
    assert latest_checkpoint(str(tmp_path)) == cks[-1][1]
    assert list_checkpoints(str(tmp_path / "missing")) == []


# -- admission dedupe + rid reservation ---------------------------------------


def test_queue_dedupes_by_rid_and_reserves_ceiling():
    q = AdmissionQueue()
    r = lm_request([1, 2], 2, arrival=0.0)
    assert q.submit(r) and q.submit(r)           # dupe swallowed, not queued
    assert q.submitted == 1 and q.duplicates == 1
    assert len(q.pending()) == 1

    reserve_rids(r.rid + 1000)
    fresh = lm_request([1], 1, arrival=0.0)
    assert fresh.rid >= r.rid + 1000             # replay-collision-free


# -- quarantine serialization -------------------------------------------------


def test_quarantine_backoff_expiry_survives_roundtrip():
    q = Quarantine(backoff=4, max_retries=3)
    q.record_failure(("lm", "sig-a"), 10, RuntimeError("boom"))
    st = q.state()
    json.dumps(st)                               # JSON-serializable as-is

    q2 = Quarantine(backoff=4, max_retries=3)
    q2.load_state(st)
    # Backoff deadlines are virtual-round numbers, so expiry lands at the
    # same round in the restored process: booked at 10, backoff 4.
    assert q2.blocks(("lm", "sig-a"), 13)
    assert not q2.blocks(("lm", "sig-a"), 14)
    assert q2.events == 1

    # Second consecutive failure after restore doubles the backoff window —
    # the fail count carried over, not just the deadline.
    q2.record_failure(("lm", "sig-a"), 14, RuntimeError("boom"))
    assert q2.blocks(("lm", "sig-a"), 21)
    assert not q2.blocks(("lm", "sig-a"), 22)


def test_quarantine_permanent_cap_survives_roundtrip():
    q = Quarantine(backoff=2, max_retries=1)
    q.record_failure("sig", 0, RuntimeError("x"))
    q.record_failure("sig", 5, RuntimeError("x"))   # past cap: permanent
    assert q.permanent() == 1
    st = q.state()
    assert st["entries"][0]["until"] is None        # inf encodes as null
    q2 = Quarantine(backoff=2, max_retries=1)
    q2.load_state(st)
    assert q2.permanent() == 1
    assert q2.blocks("sig", 10**9)
    assert math.isinf(q2._entries[next(iter(q2._entries))]["until"])


def test_quarantine_survives_engine_snapshot_restore(workloads):
    eng = ServeEngine(dict(workloads), compiled=True, bucketed=True,
                      continuous=True, max_slots=4)
    eng.quarantine.record_failure(("tree", "sig-x"), 2, RuntimeError("boom"))
    restored = restore_engine(snapshot_engine(eng), dict(workloads))
    assert restored.quarantine.blocks(("tree", "sig-x"), 3)
    assert restored.quarantine.events == 1


# -- kill + restore equivalence (single device) -------------------------------


def test_kill_restore_reproduces_uninterrupted_run(workloads, tmp_path):
    trace = _trace(workloads, seed=3)
    clean = ServeEngine(dict(workloads), compiled=True, bucketed=True,
                        continuous=True, max_slots=4)
    clean.submit_many(trace)
    clean_stats = clean.run()

    trace2 = _trace(workloads, seed=3)
    eng = ServeEngine(dict(workloads), compiled=True, bucketed=True,
                      continuous=True, max_slots=4,
                      fault_injector=FaultInjector(crash_rounds=[4]),
                      checkpoint_dir=str(tmp_path), checkpoint_every=2)
    eng.submit_many(trace2)
    with pytest.raises(InjectedCrash):
        eng.run()
    assert latest_checkpoint(str(tmp_path)) is not None

    r_eng = ServeEngine.restore(latest_checkpoint(str(tmp_path)),
                                dict(workloads))
    assert r_eng._round == 4
    r_eng.submit_many(trace2)        # full-trace replay: all dupes swallowed
    r_stats = r_eng.run()
    assert r_eng.queue.duplicates >= len(trace2)
    assert r_stats.requests_failed == 0
    assert r_stats.n_restores == 1 and r_stats.n_checkpoints >= 1
    assert r_stats.tokens_out == clean_stats.tokens_out
    _assert_equivalent(_ledger(r_eng), _ledger(clean))


def test_restore_mismatch_dumps_flight_recorder(workloads, tmp_path):
    eng = ServeEngine(dict(workloads), compiled=True, bucketed=True,
                      continuous=True, max_slots=4)
    p = str(tmp_path / "c.json")
    eng.checkpoint(path=p)
    doc = json.load(open(p))
    doc["payload"]["clock"]["round"] = 99        # tamper
    json.dump(doc, open(p, "w"))

    obs = Obs(tracer=Tracer(enabled=True, ring=4),
              metrics=MetricsRegistry(), flight=FlightRecorder(ring=2))
    with pytest.raises(CheckpointError):
        ServeEngine.restore(p, dict(workloads), obs=obs)
    assert obs.flight.dumps
    assert obs.flight.dumps[-1]["reason"] == "restore_mismatch"
    assert obs.flight.dumps[-1]["info"]["path"] == p


# -- XLA cache dir hardening (launch/jaxcache.py) -----------------------------


def test_audit_cache_dir_quarantines_corrupt_entries(tmp_path):
    good = tmp_path / "entry_good-cache"
    good.write_bytes(b"xla!")
    (tmp_path / "entry_torn-cache").write_bytes(b"")   # crash residue
    lock = tmp_path / ".lockfile"       # JAX's own, zero bytes by design
    lock.write_bytes(b"")
    with pytest.warns(RuntimeWarning, match="quarantined corrupt"):
        moved = audit_cache_dir(str(tmp_path))
    assert len(moved) == 1 and QUARANTINE_SUBDIR in moved[0]
    assert good.exists() and lock.exists()
    assert not (tmp_path / "entry_torn-cache").exists()
    assert (tmp_path / QUARANTINE_SUBDIR / "entry_torn-cache").exists()
    assert audit_cache_dir(str(tmp_path / "missing")) == []


def test_enable_cache_refuses_non_directory(tmp_path, monkeypatch):
    f = tmp_path / "not_a_dir"
    f.write_text("x")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(f))
    with pytest.warns(RuntimeWarning, match="not a directory"):
        assert enable_compilation_cache() is False


def test_cache_dir_env_wins_else_fixed_checkout_path(tmp_path, monkeypatch):
    import os

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cache_dir() == str(tmp_path)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert cache_dir() == os.path.join(root, ".jax_cache")
    assert cache_dir() == cache_dir()   # never a per-call temp name


# -- elastic mesh resize (multi-device) ---------------------------------------


@needs_devices
def test_shard_loss_evacuates_and_completes(workloads):
    trace = _trace(workloads, n=10, seed=5)
    clean = ServeEngine(dict(workloads), compiled=True, bucketed=True,
                        continuous=True, max_slots=4, n_shards=2)
    clean.submit_many(trace)
    clean_stats = clean.run()

    trace2 = _trace(workloads, n=10, seed=5)
    eng = ServeEngine(dict(workloads), compiled=True, bucketed=True,
                      continuous=True, max_slots=4, n_shards=2,
                      fault_injector=FaultInjector(shard_lost={3: 1}))
    eng.submit_many(trace2)
    stats = eng.run()

    assert stats.requests_failed == 0
    assert all(r.status == COMPLETED for r in trace2)
    assert stats.n_resize_events == 1
    ev = eng.resize_log[0]
    assert (ev["old"], ev["new"], ev["round"]) == (2, 1, 3)
    assert stats.n_entries_evacuated == ev["evacuated"] + ev["parked"]
    assert stats.tokens_out == clean_stats.tokens_out
    _assert_equivalent(_ledger(eng), _ledger(clean))


@needs_devices
def test_parked_entries_resume_token_streams_exactly(workloads):
    # Saturate both shards' slots with long decodes, then kill shard 1:
    # the survivor has no free slots, so every displaced entry must park
    # and later resume its stream mid-decode from the stashed rows.
    def lm_trace():
        return [lm_request([i + 1, i + 2, i + 3], 6, arrival=float(i // 4))
                for i in range(8)]

    clean = ServeEngine({"lm": workloads["lm"]}, compiled=True,
                        bucketed=True, continuous=True, max_slots=4,
                        n_shards=2)
    clean.submit_many(lm_trace())
    clean.run()

    trace = lm_trace()
    eng = ServeEngine({"lm": workloads["lm"]}, compiled=True, bucketed=True,
                      continuous=True, max_slots=4, n_shards=2,
                      fault_injector=FaultInjector(shard_lost={4: 1}))
    eng.submit_many(trace)
    stats = eng.run()

    assert stats.requests_failed == 0
    assert eng.resize_log[0]["parked"] >= 1
    assert all(r.status == COMPLETED for r in trace)
    _assert_equivalent(_ledger(eng), _ledger(clean))


@needs_devices
def test_work_stealing_rebalances_without_changing_outputs(workloads):
    def lm_trace():
        # Staggered arrivals: early finishers free shard-0 slots, leaving
        # the later wave imbalanced for the stealer to close.
        return [lm_request([i + 1, i + 2], 3 + (i % 3) * 2,
                           arrival=float(i)) for i in range(10)]

    clean = ServeEngine({"lm": workloads["lm"]}, compiled=True,
                        bucketed=True, continuous=True, max_slots=4,
                        n_shards=2)
    clean.submit_many(lm_trace())
    clean.run()

    trace = lm_trace()
    eng = ServeEngine({"lm": workloads["lm"]}, compiled=True, bucketed=True,
                      continuous=True, max_slots=4, n_shards=2,
                      steal_threshold=0)
    eng.submit_many(trace)
    stats = eng.run()

    assert stats.n_entries_stolen >= 1
    assert all(r.status == COMPLETED for r in trace)
    _assert_equivalent(_ledger(eng), _ledger(clean))


@needs_devices
def test_restore_on_shrunken_mesh_then_regrow(workloads, tmp_path):
    trace = _trace(workloads, n=10, seed=7)
    clean = ServeEngine(dict(workloads), compiled=True, bucketed=True,
                        continuous=True, max_slots=4, n_shards=2)
    clean.submit_many(trace)
    clean.run()

    trace2 = _trace(workloads, n=10, seed=7)
    eng = ServeEngine(dict(workloads), compiled=True, bucketed=True,
                      continuous=True, max_slots=4, n_shards=2,
                      fault_injector=FaultInjector(shard_lost={3: 0},
                                                   crash_rounds=[5]),
                      checkpoint_dir=str(tmp_path), checkpoint_every=2)
    eng.submit_many(trace2)
    with pytest.raises(InjectedCrash):
        eng.run()

    # The crash checkpoint was taken at K=1 with a device excluded; the
    # restored engine must come back on the same shrunken mesh, then
    # recover to full strength and still reproduce the clean outputs.
    r_eng = ServeEngine.restore(
        latest_checkpoint(str(tmp_path)), dict(workloads),
        fault_injector=FaultInjector(shard_back_rounds=[7]))
    assert r_eng.n_shards == 1 and r_eng._excluded_devices
    r_eng.submit_many(trace2)
    stats = r_eng.run()

    assert r_eng.n_shards == 2 and not r_eng._excluded_devices
    assert stats.requests_failed == 0
    assert stats.n_resize_events >= 1      # the regrow, post-restore
    _assert_equivalent(_ledger(r_eng), _ledger(clean))
