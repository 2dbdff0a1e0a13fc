"""The readers of the round's phases, its off-CPU time and its GC time,
on a small hand-made span list: two rounds on the loop thread, a compile
worker's spans on another, and the next round's speculative pack stamped
``overlap``."""

import chipbench_paths  # noqa: F401  (first: the path to the benchmark)

import pytest

import harness
import tracing


def _x(name, ts, dur, tid=0, **args):
    return {"ph": "X", "name": name, "ts": float(ts), "dur": float(dur),
            "tid": tid, "args": args}


def _round(t, cpu_ms, gc_ms):
    """One 1000 us round at ``t``: its phases, nested as the engine nests
    them, with a collection inside the commit."""
    return [
        _x("serve.round", t, 1000, round=t // 1000, cpu_ms=cpu_ms,
           gc_ms=gc_ms),
        _x("round.feed_stage", t + 10, 20),
        _x("round.lm", t + 40, 800),
        _x("round.pack", t + 45, 30, promoted=True),
        _x("round.dispatch", t + 76, 164),
        _x("round.lookup", t + 80, 60),
        _x("plan.h2d", t + 140, 10),
        _x("plan.dispatch", t + 150, 90),
        _x("round.schedule", t + 240, 40, overlap=True),
        _x("round.pack", t + 280, 100, overlap=True),
        _x("round.settle", t + 380, 130),
        _x("plan.block", t + 390, 100),
        _x("round.scatter", t + 510, 300),
        _x("round.commit", t + 515, 150),
        _x("gc.collect", t + 600, 40, gen=1, collected=3),
        _x("round.readback", t + 670, 130),
        _x("round.feed", t + 850, 100),
    ]


WORKER = [_x("plan.pack", 100, 5000, tid=1),
          _x("plan.lower", 200, 3000, tid=1),
          _x("xla.compile", 3300, 1500, tid=1, bg=True),
          _x("plan.block", 5000, 700, tid=1)]


def _ctx(events):
    return {"spans": tracing.span_self_times(events)}


@pytest.fixture
def ctx():
    return _ctx(_round(0, 0.6, 0.04) + _round(1000, 0.8, 0.0) + WORKER)


def _read(ctx, metric):
    return harness.load_module("layers", metric.partition(".")[0]).read(
        ctx, metric)


def test_phase_self_times_per_round_on_the_loop_thread(ctx):
    # pack: feed_stage 20 + promoted pack 30 + overlapped pack 100; the
    # worker's plan.pack is not the loop's.
    assert _read(ctx, "round_phase_ms.pack") == pytest.approx(0.150)
    # dispatch: the engine's 4 outside its children, lookup 60, h2d 10,
    # dispatch 90.
    assert _read(ctx, "round_phase_ms.dispatch") == pytest.approx(0.164)
    # commit: 150 less the collection inside it.
    assert _read(ctx, "round_phase_ms.commit") == pytest.approx(0.110)
    # readback: readback 130 + the loop's plan.block 100.
    assert _read(ctx, "round_phase_ms.readback") == pytest.approx(0.230)


def test_off_cpu_and_gc_per_round(ctx):
    # (1.0 - 0.6) and (1.0 - 0.8) ms off the CPU, over two rounds.
    assert _read(ctx, "offcpu_ms_per_round.itl") == pytest.approx(0.3)
    assert _read(ctx, "gc_ms_per_round.tokens") == pytest.approx(0.02)


def test_a_program_without_the_new_spans_reads_nothing():
    """What the parent records: rounds without counters, no commit span."""
    old = [dict(e, args={k: v for k, v in e["args"].items()
                         if k not in ("cpu_ms", "gc_ms")})
           for e in _round(0, 0.6, 0.04)
           if e["name"] not in ("round.commit", "round.readback",
                                "round.lookup", "round.settle",
                                "round.dispatch",
                                "gc.collect")]
    c = _ctx(old + WORKER)
    for m in ("round_phase_ms.pack", "round_phase_ms.dispatch",
              "round_phase_ms.commit", "round_phase_ms.readback",
              "offcpu_ms_per_round.itl", "gc_ms_per_round.tokens"):
        assert _read(c, m) is None, m
    assert _read(_ctx(WORKER), "round_phase_ms.pack") is None
