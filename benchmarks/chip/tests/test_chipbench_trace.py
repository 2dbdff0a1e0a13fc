"""The reduction from a profiler trace to busy time, top operations and
idle gaps by host span, on a small recorded trace."""

import chipbench_paths  # noqa: F401  (first: the path to the benchmark)

import json
import os

import pytest

import tracing

DATA = os.path.join(os.path.dirname(__file__), "data", "small_trace.json")


@pytest.fixture
def events():
    with open(DATA) as f:
        return json.load(f)["events"]


def test_busy_and_window(events):
    tr = tracing.reduce_trace(events)
    assert tr["window_s"] == pytest.approx(100000e-9)
    # copy.2 is clipped to the window's start; the two overlapping ops
    # count once.
    assert tr["busy_s"] == pytest.approx((500 + 15000 + 10000) * 1e-9)
    assert tr["n_devices"] == 1


def test_top_ops(events):
    tr = tracing.reduce_trace(events)
    ops = dict(tr["device_ops"])
    assert ops["fusion.1"] == pytest.approx(20000e-9)
    assert ops["custom-call.3"] == pytest.approx(10000e-9)
    assert tr["device_ops"][0][0] == "fusion.1"


def test_idle_gaps_by_innermost_host_span(events):
    gaps = dict(tracing.reduce_trace(events)["idle_gaps"])
    # Gaps [1.5, 30], [45, 85] and [95, 101] us, split instant by instant
    # among the spans that cover them on the window's thread.
    assert gaps == pytest.approx({"round.pack": 20000e-9,
                                  "round.scatter": 30000e-9,
                                  "plan.block": 5000e-9,
                                  "serve.round": 19500e-9})
    assert sum(gaps.values()) == pytest.approx(74500e-9)


def test_no_window_or_no_device_reads_nothing(events):
    assert tracing.reduce_trace(
        [e for e in events if e["name"] != tracing.WINDOW]) is None
    assert tracing.reduce_trace(
        [e for e in events if not e["plane"].startswith("/device")]) is None


def test_gap_outside_every_span_goes_to_the_harness():
    spans = [(10.0, 5.0, "a"), (11.0, 1.0, "b"), (20.0, 5.0, "c")]
    segs = tracing._innermost(spans, 0.0, 30.0)
    assert [(a, b, n) for a, b, n in segs] == [
        (0.0, 10.0, tracing.HOST_OUTSIDE), (10.0, 11.0, "a"),
        (11.0, 12.0, "b"), (12.0, 15.0, "a"),
        (15.0, 20.0, tracing.HOST_OUTSIDE), (20.0, 25.0, "c"),
        (25.0, 30.0, tracing.HOST_OUTSIDE)]
    assert tracing._attribute([(9.0, 11.5), (14.0, 21.0)], segs) == {
        tracing.HOST_OUTSIDE: 6.0, "a": 2.0, "b": 0.5, "c": 1.0}


def test_self_time_subtracts_direct_children():
    evs = [{"ph": "X", "name": "serve.round", "ts": 0.0, "dur": 100.0,
            "tid": 0},
           {"ph": "X", "name": "round.pack", "ts": 10.0, "dur": 30.0,
            "tid": 0},
           {"ph": "X", "name": "plan.pack", "ts": 15.0, "dur": 10.0,
            "tid": 0},
           {"ph": "X", "name": "xla.compile", "ts": 5.0, "dur": 50.0,
            "tid": 1}]
    own = {s["name"]: s["self_us"] for s in tracing.span_self_times(evs)}
    assert own == {"serve.round": 70.0, "round.pack": 20.0,
                   "plan.pack": 10.0, "xla.compile": 50.0}
