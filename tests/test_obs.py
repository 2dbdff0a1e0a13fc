"""Observability (repro.obs, DESIGN.md §6): tracer span nesting/balance,
Chrome trace-event export validity, metrics-registry percentile math vs
numpy, flight-recorder dumps on injected faults, disabled-path no-ops under
thread hammering, and ServeStats <-> metrics cross-validation on a real
engine run."""

import gc
import glob
import json
import threading

import numpy as np
import pytest

from repro.models.workloads import make_workload
from repro.obs import FlightRecorder, Obs, Tracer, metrics
from repro.obs.metrics import (Histogram, MetricsRegistry, latency_summary,
                               percentile)
from repro.obs import tracer as tracer_mod
from repro.obs.tracer import (NULL_SPAN, NULL_TRACER, validate_chrome_trace)
from repro.serve import ServeEngine, lm_request
from repro.serve.faults import FaultInjector, Quarantine, poison_requests
from repro.serve.queue import FAILED, TIMED_OUT

MODEL_SIZE = 8


@pytest.fixture(scope="module")
def lm_workloads():
    return {"lm": make_workload("ChainLM", MODEL_SIZE)}


def _lm_trace(n=4, max_new=3):
    nrng = np.random.default_rng(0)
    return [lm_request(list(map(int, nrng.integers(0, 256, 3 + i % 3))),
                       max_new, arrival=float(i)) for i in range(n)]


def _serve(workloads, reqs, **kw):
    eng = ServeEngine(workloads, compiled=True, bucketed=True,
                      continuous=True, max_slots=4, **kw)
    eng.submit_many(reqs)
    return eng, eng.run()


# -- tracer ------------------------------------------------------------------


def test_span_nesting_depth_and_balance():
    tr = Tracer(enabled=True)
    with tr.span("a"):
        assert tr.depth() == 1
        with tr.span("b"):
            assert tr.depth() == 2
        tr.event("ev", x=1)
    assert tr.depth() == 0
    assert tr.open_spans() == 0
    names = [e["name"] for e in tr.events if e["cat"] != "gc"]
    assert names == ["b", "ev", "a"]     # spans record on exit
    a, b = tr.spans("a")[0], tr.spans("b")[0]
    assert a["ts"] <= b["ts"]
    assert a["ts"] + a["dur"] >= b["ts"] + b["dur"]


def test_span_balanced_even_when_body_raises():
    tr = Tracer(enabled=True)
    with pytest.raises(ValueError):
        with tr.span("outer"):
            with tr.span("inner"):
                raise ValueError("boom")
    assert tr.open_spans() == 0
    assert [s["name"] for s in tr.spans() if s["cat"] != "gc"] == [
        "inner", "outer"]


def test_ring_keeps_last_rounds_and_counts_drops():
    tr = Tracer(enabled=True, ring=3)
    for r in range(6):
        tr.mark_round(r)
        tr.event("tick", round=r)
    rounds = [b["round"] for b in tr.recent_rounds(10)]
    assert rounds == [3, 4, 5]
    assert tr.n_dropped == 3
    assert all(len(b["events"]) == 1 for b in tr.recent_rounds(10))


def test_chrome_export_schema_and_json_safety():
    tr = Tracer(enabled=True)
    with tr.span("s", weird=object(), ok=1, nested={"k": (1, 2)}):
        tr.event("e", arr=np.arange(3))
    obj = tr.to_chrome()
    assert validate_chrome_trace(obj) == []
    json.dumps(obj)                       # round-trips
    phases = {e["ph"] for e in obj["traceEvents"]}
    assert {"M", "X", "i"} <= phases


def test_validate_chrome_trace_flags_malformed():
    assert validate_chrome_trace([]) != []
    assert validate_chrome_trace({"traceEvents": [{"ph": "Z"}]}) != []
    bad_dur = {"traceEvents": [{"ph": "X", "name": "s", "pid": 0, "tid": 0,
                                "ts": 0.0, "dur": -1.0}]}
    assert any("dur" in p for p in validate_chrome_trace(bad_dur))


def test_disabled_tracer_is_noop():
    tr = Tracer(enabled=False)
    sp = tr.span("x", arg=1)
    assert sp is NULL_SPAN               # shared singleton: no allocation
    with sp:
        sp.set(anything=2)
    tr.event("e")
    tr.mark_round(0)
    assert tr.events == []
    assert tr.open_spans() == 0
    assert NULL_TRACER.span("y") is NULL_SPAN


@pytest.mark.parametrize("enabled", [False, True])
def test_tracer_thread_hammer_stays_balanced(enabled):
    tr = Tracer(enabled=enabled)
    errs = []

    def work(tid):
        try:
            for i in range(200):
                with tr.span("outer", tid=tid):
                    with tr.span("inner", i=i):
                        pass
                    tr.event("ev", tid=tid)
                assert tr.depth() == 0
        except Exception as exc:          # pragma: no cover
            errs.append(exc)

    threads = [threading.Thread(target=work, args=(t,)) for t in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert errs == []
    assert tr.open_spans() == 0
    n = len(tr.spans("outer")) + len(tr.spans("inner"))
    assert n == (8 * 200 * 2 if enabled else 0)
    if enabled:
        assert validate_chrome_trace(tr.to_chrome()) == []


# -- the runtime beside the program ------------------------------------------


def _runtime_hooks():
    """What the tracer may hook: ``gc.callbacks`` and JAX's listeners."""
    import jax._src.monitoring as mon

    return (list(gc.callbacks), mon.get_event_time_span_listeners(),
            mon.get_event_duration_listeners(), mon.get_event_listeners())


@pytest.mark.parametrize("gen", [1, 2])
def test_gc_collect_span_and_collection_under_the_tracer_lock(gen):
    """A collection shows up as a ``gc.collect`` span, and one that starts
    while its own thread holds the tracer's lock (as ``events`` or
    ``mark_round`` do when they allocate) completes instead of
    deadlocking in the hook."""
    tr = Tracer(enabled=True)
    done = []

    def body():
        with tr._lock:
            gc.collect(gen)
        done.append(True)

    t = threading.Thread(target=body, daemon=True)
    t.start()
    t.join(timeout=60)
    assert not t.is_alive() and done
    spans = tr.spans("gc.collect")
    assert spans and spans[-1]["args"]["gen"] == gen
    assert "collected" in spans[-1]["args"]
    assert tr.gc_s > 0
    tr.enabled = False
    assert tr.open_spans() == 0


def test_tracer_thread_hammer_with_collections_stays_balanced():
    """Eight threads open spans while collections run on them: the GC
    hook's one open-collection slot and the re-entrant lock keep every
    stack balanced, and each generation-1/2 collection is one span."""
    tr = Tracer(enabled=True)
    seen = []

    def count(phase, info):
        if phase == "stop" and info["generation"] >= 1:
            seen.append(info["generation"])

    gc.callbacks.append(count)      # after the tracer's hook: sees no more
    errs = []

    def work(tid):
        try:
            for i in range(200):
                with tr.span("outer", tid=tid):
                    with tr.span("inner", i=i):
                        junk = [[j] for j in range(50)]
                    if i % 25 == tid % 25:
                        gc.collect(1 + i % 2)
                del junk
                assert tr.depth() == 0
        except Exception as exc:          # pragma: no cover
            errs.append(exc)

    threads = [threading.Thread(target=work, args=(t,)) for t in range(8)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        gc.callbacks.remove(count)
        tr.enabled = False
    assert errs == [] and not any(t.is_alive() for t in threads)
    assert tr.open_spans() == 0
    assert len(tr.spans("outer")) == len(tr.spans("inner")) == 8 * 200
    collects = tr.spans("gc.collect")
    assert seen        # a collect that meets another one running is skipped
    assert len(collects) == len(seen)
    assert sorted(s["args"]["gen"] for s in collects) == sorted(seen)
    assert validate_chrome_trace(tr.to_chrome()) == []


def test_ring_tracer_installs_no_hooks_and_stamps_no_counters():
    """The flight recorder's always-on ring tracer records program spans
    only: no GC hook, no JAX listener, no per-round counters."""
    gc.collect()
    before = _runtime_hooks()
    tr = Tracer(enabled=True, ring=4)
    assert _runtime_hooks() == before
    tr.mark_round(0)
    with tr.counted_span("serve.round", round=0):
        gc.collect(2)
    tr.enabled = False
    assert _runtime_hooks() == before
    assert [s["name"] for s in tr.spans()] == ["serve.round"]
    assert tr.spans("serve.round")[0]["args"] == {"round": 0}


def test_disabled_tracer_leaves_gc_and_jax_listeners_as_they_were():
    gc.collect()          # finalize any dropped tracer before the snapshot
    before = _runtime_hooks()
    tr = Tracer(enabled=False)
    with tr.span("x"):
        gc.collect()
    assert _runtime_hooks() == before
    tr.enabled = True
    assert _runtime_hooks() != before
    tr.enabled = False
    assert _runtime_hooks() == before
    tr.enabled = True
    tr.enabled = False
    assert _runtime_hooks() == before
    assert tr.spans("x") == []


def test_jit_retrace_in_a_traced_round_is_a_jax_trace_span():
    """A jit trace and compile inside an engine round land as
    ``jax.trace`` / ``jax.compile`` spans naming the function and the
    round. Width 12 is this test's own, so the engine's jitted helpers
    trace anew here."""
    tr = Tracer(enabled=True)
    _serve({"lm": make_workload("ChainLM", 12)}, _lm_trace(n=2, max_new=2),
           obs=Obs(tracer=tr))
    tr.enabled = False
    rounds = {s["args"]["round"] for s in tr.spans("serve.round")}
    for name in ("jax.trace", "jax.compile"):
        spans = tr.spans(name)
        assert spans, name
        assert all(s["args"]["fun_name"] for s in spans)
        assert any(s["args"]["round"] in rounds for s in spans)
    assert any("_fused_zero" in s["args"]["fun_name"]
               and s["args"]["round"] in rounds
               for s in tr.spans("jax.trace"))
    assert validate_chrome_trace(tr.to_chrome()) == []


def test_annotate_writes_span_names_into_the_profiler_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 2
    tr = Tracer(enabled=True, annotate=True)
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with tr.span("serve.round", round=0):
            with tr.span("round.commit"):
                jnp.arange(4.0).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
        tr.enabled = False
    paths = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert paths
    names = {ev.name for plane in
             jax.profiler.ProfileData.from_file(paths[0]).planes
             for line in plane.lines for ev in line.events}
    assert {"serve.round", "round.commit"} <= names
    assert [s["name"] for s in tr.spans() if s["cat"] == "serve"] == [
        "round.commit", "serve.round"]


# -- metrics -----------------------------------------------------------------


def test_percentile_matches_numpy():
    nrng = np.random.default_rng(7)
    for size in (1, 2, 5, 100, 997):
        xs = nrng.lognormal(0.0, 2.0, size).tolist()
        for q in (0, 10, 50, 90, 95, 99, 100):
            assert percentile(xs, q) == pytest.approx(
                float(np.percentile(xs, q)), rel=1e-12, abs=1e-12)
    assert percentile([], 50) == 0.0
    assert latency_summary([1.0, 2.0, 3.0]) == {
        "p50": 2.0, "p95": pytest.approx(2.9), "p99": pytest.approx(2.98)}


def test_histogram_buckets_and_percentiles():
    reg = MetricsRegistry()
    h = reg.histogram("lat", boundaries=(0.1, 1.0, 10.0))
    xs = [0.05, 0.5, 0.5, 5.0, 50.0]
    for x in xs:
        h.observe(x)
    snap = h.snapshot()
    assert snap["count"] == 5
    assert snap["sum"] == pytest.approx(sum(xs))
    assert snap["min"] == 0.05 and snap["max"] == 50.0
    assert snap["buckets"] == {"le_0.1": 1, "le_1": 3, "le_10": 4,
                               "le_inf": 5}
    for q in (50, 95, 99):
        assert snap[f"p{q}"] == pytest.approx(float(np.percentile(xs, q)))


def test_histogram_retains_a_bounded_window_of_samples(monkeypatch):
    """Past ``MAX_SAMPLES`` observations the percentiles are the latest
    window's, exact; count, sum, min, max and buckets cover them all."""
    monkeypatch.setattr(metrics, "MAX_SAMPLES", 8)
    h = Histogram("lat", threading.Lock(), boundaries=(5.0, 15.0))
    for x in range(20):
        h.observe(float(x))
    assert len(h.samples) == 8
    snap = h.snapshot()
    assert (snap["count"], snap["sum"], snap["min"], snap["max"]) == (
        20, 190.0, 0.0, 19.0)
    assert snap["buckets"] == {"le_5": 6, "le_15": 16, "le_inf": 20}
    assert snap["p50"] == pytest.approx(float(np.percentile(range(12, 20),
                                                            50)))


def test_registry_get_or_create_and_kind_mismatch():
    reg = MetricsRegistry()
    c = reg.counter("n")
    assert reg.counter("n") is c
    c.inc()
    c.inc(2.5)
    reg.gauge("g").set(4)
    with pytest.raises(TypeError):
        reg.gauge("n")
    snap = reg.snapshot()
    assert snap["counters"]["n"] == 3.5
    assert snap["gauges"]["g"] == 4.0
    reg.counter("i").inc(2)
    assert reg.snapshot()["counters"]["i"] == 2   # integral stays int
    json.dumps(reg.snapshot())


def test_metrics_thread_hammer():
    reg = MetricsRegistry()

    def work():
        for i in range(500):
            reg.counter("c").inc()
            reg.histogram("h").observe(i * 1e-3)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = reg.snapshot()
    assert snap["counters"]["c"] == 8 * 500
    assert snap["histograms"]["h"]["count"] == 8 * 500


# -- flight recorder ---------------------------------------------------------


def test_flight_dump_snapshots_ring(tmp_path):
    tr = Tracer(enabled=True, ring=3)
    for r in range(5):
        tr.mark_round(r)
        tr.event("tick", round=r)
    fl = FlightRecorder(ring=2, out_dir=str(tmp_path))
    rec = fl.dump(tr, "failed", rid=7, detail=object())
    assert rec["reason"] == "failed"
    assert [b["round"] for b in rec["rounds"]] == [3, 4]
    files = list(tmp_path.iterdir())
    assert len(files) == 1 and "failed" in files[0].name
    on_disk = json.loads(files[0].read_text())
    assert on_disk["info"]["rid"] == 7
    json.dumps(rec)


# -- quarantine callback -----------------------------------------------------


def test_quarantine_on_event_fires_per_booking():
    seen = []
    q = Quarantine(backoff=2, max_retries=2,
                   on_event=lambda *a: seen.append(a))
    exc = RuntimeError("x")
    q.record_failure("sig", 0, exc)
    q.record_failure("sig", 5, exc)
    q.record_failure("sig", 9, exc)      # past max_retries: permanent
    assert [s[:2] for s in seen] == [("sig", 1), ("sig", 2), ("sig", 3)]
    assert seen[-1][2] == float("inf")
    assert all(s[3] == repr(exc) for s in seen)
    assert q.events == 3


# -- engine integration ------------------------------------------------------


def test_engine_trace_covers_rounds_and_stats_match(lm_workloads):
    tr = Tracer(enabled=True)
    # Fresh registry: the process-default one accumulates counts from every
    # other engine test in the session, breaking exact cross-validation.
    eng, stats = _serve(lm_workloads, _lm_trace(),
                        obs=Obs(tracer=tr, metrics=MetricsRegistry()))
    assert tr.open_spans() == 0
    assert validate_chrome_trace(tr.to_chrome()) == []
    assert len(tr.spans("serve.run")) == 1
    assert len(tr.spans("serve.round")) >= stats.n_rounds
    # every compile span attributed to a signature with its wall
    for c in tr.spans("xla.compile"):
        assert c["args"].get("bucket") or c["args"].get("sig")
        assert c["args"]["lower_s"] > 0
    assert len(tr.spans("xla.compile")) == stats.n_compiles
    # metrics agree with ServeStats
    snap = eng.metrics.snapshot()
    assert snap["counters"]["serve.requests_completed"] == stats.requests_done
    assert snap["counters"]["serve.tokens_out"] == stats.tokens_out
    assert snap["counters"]["serve.rounds"] == stats.n_rounds
    assert snap["gauges"]["serve.wall_s"] == pytest.approx(stats.wall_s)
    assert snap["gauges"]["serve.n_compiles"] == stats.n_compiles
    assert (snap["histograms"]["serve.latency_s"]["count"]
            == stats.requests_done)
    # request lifecycle instants present for each completed request
    done = [e for e in tr.events if e["name"] == "req.completed"]
    assert len(done) == stats.requests_done


def test_pipelined_lm_rounds_have_phase_spans_and_round_counters(
        lm_workloads):
    """Every lm round of a pipelined CPU run names its dispatch-side
    lookup, its commit and its readback, and ``serve.round`` carries the
    loop thread's CPU and GC time."""
    tr = Tracer(enabled=True)
    _, stats = _serve(lm_workloads, _lm_trace(n=6, max_new=4),
                      obs=Obs(tracer=tr), pipeline=True)
    tr.enabled = False
    assert stats.n_pipelined_rounds > 0
    spans = tr.spans()
    lms = [s for s in spans if s["name"] == "round.lm"]
    assert len(lms) == stats.n_rounds

    def inside(outer, name):
        return [s for s in spans if s["name"] == name
                and s["tid"] == outer["tid"] and outer["ts"] <= s["ts"]
                and s["ts"] + s["dur"] <= outer["ts"] + outer["dur"]]

    for lm in lms:
        for name in ("round.lookup", "round.commit", "round.readback"):
            assert len(inside(lm, name)) == 1, name
    for sc in tr.spans("round.scatter"):
        assert inside(sc, "round.commit") and inside(sc, "round.readback")
    for r in tr.spans("serve.round"):
        assert r["args"]["cpu_ms"] >= 0 and r["args"]["gc_ms"] >= 0
        if tracer_mod.resource is not None:    # the host counts them
            assert {"nivcsw", "majflt"} <= set(r["args"])
    assert len(tr.spans("serve.poll_compiles")) == len(tr.spans("serve.round"))
    assert tr.open_spans() == 0


def test_engine_default_obs_records_nothing(lm_workloads):
    eng, stats = _serve(lm_workloads, _lm_trace(n=2))
    assert stats.requests_done == 2
    assert eng.tracer.events == []        # default tracer stays disabled
    assert eng.flight is None


def test_flight_dump_for_every_failed_and_timed_out(lm_workloads):
    injector = FaultInjector.from_spec("poison=2")
    reqs = _lm_trace(n=3, max_new=2)
    for r in reqs:
        r.deadline = r.arrival + 3.0      # prefill alone exceeds this
    wl = dict(lm_workloads)
    wl["tree"] = make_workload("TreeLSTM", MODEL_SIZE)
    poisoned = poison_requests(2, family="tree", arrival=0.0)
    eng, stats = _serve(wl, reqs + poisoned, fault_injector=injector)
    bad = [r for r in reqs + poisoned if r.status in (FAILED, TIMED_OUT)]
    assert bad, "fault mix must produce terminal failures"
    assert eng.flight is not None         # auto-created under injection
    fails = [d for d in eng.flight.dumps
             if d["reason"] in ("failed", "timed_out")]
    assert len(fails) == len(bad)
    assert all(d["rounds"] for d in fails)    # each dump carries trace
    rids = {d["info"]["rid"] for d in fails}
    assert rids == {r.rid for r in bad}


def test_serve_stats_percentiles_use_shared_helper(lm_workloads):
    _, stats = _serve(lm_workloads, _lm_trace())
    pct = stats.latency_percentiles()
    assert set(pct) == {"p50_latency_s", "p95_latency_s", "p99_latency_s",
                        "p50_ttft_s", "p95_ttft_s"}
    assert pct["p50_latency_s"] == pytest.approx(
        float(np.percentile(stats.latency_s, 50)))
    assert pct["p99_latency_s"] == pytest.approx(
        float(np.percentile(stats.latency_s, 99)))
    assert pct["p50_ttft_s"] == pytest.approx(
        float(np.percentile(stats.ttft_s, 50)))


# -- fig8 --from-trace --------------------------------------------------------


def test_fig8_from_trace_decomposition(tmp_path, lm_workloads):
    from benchmarks.fig8_decomposition import decompose_trace, span_self_times

    tr = Tracer(enabled=True)
    _, stats = _serve(lm_workloads, _lm_trace(), obs=Obs(tracer=tr))
    path = tmp_path / "trace.json"
    tr.write(str(path))
    d = decompose_trace(str(path))
    for k in ("schedule_ms", "memory_ms", "execution_ms", "compile_ms",
              "other_ms"):
        assert d[k] >= 0.0
    # self time never exceeds duration, and the components sum to the total
    spans = span_self_times(tr.to_chrome()["traceEvents"])
    assert all(s["self_us"] <= s["dur"] + 1e-6 for s in spans)
    total = (d["schedule_ms"] + d["memory_ms"] + d["execution_ms"]
             + d["compile_ms"] + d["other_ms"])
    assert total == pytest.approx(d["total_ms"])
    # named component spans cover >= 90% of the serve wall (the obs
    # acceptance bar; engine containers contribute only self-time slack)
    assert d["coverage"] >= 0.9
