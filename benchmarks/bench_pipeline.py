"""Round-pipelining benchmark: the DESIGN.md §9 acceptance gate.

Serves the same lm traces through a serial engine (``pipeline=False``:
pack -> dispatch -> block each round on the loop) and a pipelined one
(while round t's bucket program is in flight on device, the host plans
and packs round t+1; commit promotes the speculative pack when the
prediction held). One persistent engine per mode is warmed first (XLA
compiles, plan/pack caches), then each timed pass resubmits the same
trace shifted past the engine's virtual clock — so the passes measure
the steady-state serve loop, not per-engine first-touch costs.

Gates:

- **churn_faster** / **poisson_faster**: median-of-``--reps`` pipelined
  rounds/s against serial on a constant-arrival churn trace (staggered
  admissions + a prefill-length mix keep the per-round composition
  moving) and on a Poisson trace. The median is the gate estimator —
  one lucky pass moves a best-of floor by the full noise amplitude,
  while a real pipelining win shifts the whole distribution. The bar is
  host-aware: with >= 2 CPUs the XLA device threads run beside the serve
  loop, a real in-flight window exists, and pipelined must be strictly
  faster; on a single-CPU host the "device" computes on the same core
  the host packs on, overlap cannot shorten wall clock by construction,
  and the gate degrades to no-regression (pipelined >= 97% of serial —
  the speculation/snapshot machinery must be ~free). The JSON records
  which bar applied (``wall_gate``).
- **bit_identical**: pipelined token streams equal the serial engine's on
  every pass of both traces, position-aligned by submission order (the
  rid counter is process-global, so cross-run comparison keys on rank,
  never on raw rid).
- **pack_overlap**: in a recorded warm trace, >= 50% of ``round.pack``
  self time carries the ``overlap`` stamp — packing actually ran while
  the previous dispatch was in flight, off the serve loop's critical
  path. This is the structural claim and it holds on any host: the spans
  record *where in the loop* the work ran, not how the OS scheduled it.
  ``round.feed_stage`` (slot staging, unavoidable commit work) is split
  out of ``round.pack`` by the engine and not counted against the
  pipeline.

    PYTHONPATH=src python -m benchmarks.bench_pipeline [--out BENCH_pipeline.json]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import time

from repro.models.workloads import SERVE_FAMILIES, make_workload
from repro.obs import Obs, Tracer
from repro.serve import ServeEngine, synth_trace
from repro.launch.jaxcache import enable_compilation_cache

from .common import (add_obs_args, emit, maybe_enable_obs, platform_payload,
                     write_obs)
from .fig8_decomposition import overlap_fraction, span_self_times


def _workloads(model_size: int, seed: int) -> dict:
    return {"lm": make_workload(SERVE_FAMILIES["lm"], model_size, seed)}


def _trace(workloads, n: int, max_new: int, seed: int, arrivals: str):
    # rate 2/round staggers admissions across the run and the 2..12 prompt
    # spread mixes prefill lengths: the per-round composition keeps
    # changing, so packing stays a real per-round cost (PR 3 made churn
    # "host-side packing, not a recompile" — this trace leans on that).
    return synth_trace(["lm"], n, 2.0, max_new, workloads, seed,
                       arrivals=arrivals, prompt_lo=2, prompt_hi=12)


def _tokens(reqs) -> list:
    return [r.out for r in sorted(reqs, key=lambda r: r.rid)]


def _cpus() -> int:
    try:
        return len(os.sched_getaffinity(0))
    except AttributeError:
        return os.cpu_count() or 1


def _wall_bar() -> tuple[float, str]:
    """(threshold, label) for the rounds/s gate — see the module docstring."""
    if _cpus() >= 2:
        return 1.0, "strictly-faster"
    return 0.97, "no-regression(single-cpu)"


def _warm_engine(wl, requests, max_new, seed, arrivals, pipeline,
                 max_slots):
    eng = ServeEngine(dict(wl), compiled=True, bucketed=True,
                      continuous=True, max_slots=max_slots,
                      pipeline=pipeline)
    reqs = _trace(wl, requests, max_new, seed, arrivals)
    eng.submit_many(reqs)
    eng.run()
    return eng


def _timed_pass(eng, wl, requests, max_new, seed, arrivals):
    """Resubmit the trace past the engine's virtual clock; time the run."""
    reqs = _trace(wl, requests, max_new, seed, arrivals)
    base = eng._now
    for r in reqs:
        r.arrival += base
    eng.submit_many(reqs)
    n0 = eng.stats.n_rounds
    t0 = time.perf_counter()
    eng.run()
    wall = time.perf_counter() - t0
    return (eng.stats.n_rounds - n0), wall, _tokens(reqs)


def _measure(wl, requests, max_new, seed, arrivals, passes, max_slots):
    """Warm one persistent engine per mode, then interleave timed
    resubmission passes; per-mode best-of floors and cross-mode token
    comparison per pass."""
    engines = {mode: _warm_engine(wl, requests, max_new, seed, arrivals,
                                  pipeline, max_slots)
               for mode, pipeline in (("serial", False),
                                      ("pipelined", True))}
    rows = {m: [] for m in engines}
    identical = True
    for _ in range(passes):
        pass_toks = {}
        for mode, eng in engines.items():
            n_rounds, wall, toks = _timed_pass(eng, wl, requests, max_new,
                                               seed, arrivals)
            rows[mode].append({
                "wall_s": wall, "n_rounds": n_rounds,
                "rounds_per_s": n_rounds / wall if wall else 0.0,
            })
            pass_toks[mode] = toks
        identical = identical and pass_toks["serial"] == \
            pass_toks["pipelined"]
    st = engines["pipelined"].stats
    counters = {"pipelined_rounds": st.n_pipelined_rounds,
                "overlapped_packs": st.n_overlapped_packs,
                "spec_cancelled": st.n_spec_cancelled}
    for eng in engines.values():
        eng.close()
    best = {m: max(r["rounds_per_s"] for r in rows[m]) for m in rows}
    med = {m: statistics.median(r["rounds_per_s"] for r in rows[m])
           for m in rows}
    bar, bar_name = _wall_bar()
    # Median, not best-of: one lucky pass shifts a best-of floor by the
    # full noise amplitude, while a real pipelining win shifts the whole
    # distribution. The per-pass rows stay in the payload for inspection.
    return {"passes": rows, "best_rounds_per_s": best,
            "median_rounds_per_s": med,
            "bit_identical": identical, "wall_gate": bar_name,
            "counters": counters,
            "faster": med["pipelined"] > med["serial"] * bar}


def _overlap_trace(wl, requests, max_new, seed, max_slots):
    """Warm pack-overlap attribution: run the churn trace once untraced on
    a pipelined engine, then resubmit it (arrivals shifted past the
    engine's virtual clock) with the tracer on. The second run's packs are
    steady-state — what the pipeline is supposed to hide."""
    tracer = Tracer(enabled=False)
    eng = ServeEngine(dict(wl), compiled=True, bucketed=True,
                      continuous=True, max_slots=max_slots, pipeline=True,
                      obs=Obs(tracer=tracer))
    first = _trace(wl, requests, max_new, seed, "constant")
    eng.submit_many(first)
    eng.run()
    again = _trace(wl, requests, max_new, seed, "constant")
    base = eng._now
    for r in again:
        r.arrival += base
    tracer.enabled = True
    eng.submit_many(again)
    stats = eng.run()
    eng.close()
    if _tokens(first) != _tokens(again):
        return {"pack_overlap_frac": 0.0, "error": "warm rerun diverged"}
    spans = span_self_times(tracer.events)
    packs = [s for s in spans if s["name"] == "round.pack"]
    ov = sum(s["self_us"] for s in packs
             if s.get("args", {}).get("overlap"))
    return {"pack_overlap_frac": overlap_fraction(spans),
            "pack_self_us": sum(s["self_us"] for s in packs),
            "pack_overlapped_us": ov,
            "feed_stage_self_us": sum(s["self_us"] for s in spans
                                      if s["name"] == "round.feed_stage"),
            "pipelined_rounds": stats.n_pipelined_rounds,
            "overlapped_packs": stats.n_overlapped_packs}


def run(out: str = "", model_size: int = 512, requests: int = 48,
        max_new: int = 16, reps: int = 8, seed: int = 0,
        max_slots: int = 16) -> dict:
    wl = _workloads(model_size, seed)
    churn = _measure(wl, requests, max_new, seed, "constant", reps,
                     max_slots)
    poisson = _measure(wl, requests, max_new, seed, "poisson", reps,
                       max_slots)
    overlap = _overlap_trace(wl, requests, max_new, seed, max_slots)

    gates = {
        "churn_faster": churn["faster"],
        "poisson_faster": poisson["faster"],
        "bit_identical": churn["bit_identical"] and
        poisson["bit_identical"],
        "pack_overlap": overlap["pack_overlap_frac"] >= 0.5,
    }
    result = {
        "model_size": model_size, "requests": requests,
        "max_new": max_new, "reps": reps, "max_slots": max_slots,
        "cpus": _cpus(), "wall_gate": churn["wall_gate"],
        "churn": churn, "poisson": poisson, "overlap": overlap,
        "gates": gates, "ok": all(gates.values()),
    }
    for name, m in (("churn", churn), ("poisson", poisson)):
        s, p = m["median_rounds_per_s"]["serial"], \
            m["median_rounds_per_s"]["pipelined"]
        emit(f"bench_pipeline/{name}", 1e6 / p if p else 0.0,
             f"serial_rps={s:.1f};pipelined_rps={p:.1f};"
             f"speedup={p / s if s else 0.0:.3f}x;"
             f"gate={m['wall_gate']};"
             f"bit_identical={m['bit_identical']}")
    emit("bench_pipeline/overlap",
         overlap.get("pack_overlapped_us", 0.0),
         f"pack_overlap_frac={overlap['pack_overlap_frac']:.2f};"
         f"pipelined_rounds={overlap.get('pipelined_rounds', 0)}")
    emit("bench_pipeline/gates", 0.0,
         ";".join(f"{k}={v}" for k, v in gates.items()))
    result.update(platform_payload())
    if out:
        with open(out, "w") as f:
            json.dump(result, f, indent=1)
        print(f"# wrote {out}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_pipeline.json")
    ap.add_argument("--model-size", type=int, default=512)
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--max-slots", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    add_obs_args(ap)
    args = ap.parse_args(argv)
    enable_compilation_cache()
    maybe_enable_obs(args)
    res = run(out=args.out, model_size=args.model_size,
              requests=args.requests, max_new=args.max_new,
              reps=args.reps, seed=args.seed, max_slots=args.max_slots)
    write_obs(args)
    # CI gate (pipeline-smoke): pipelined rounds/s above the host-aware
    # bar on both traces, outputs bit-identical everywhere, and >= 50% of
    # round.pack self time attributed as overlapped in the warm trace.
    return 0 if res["ok"] else 1


if __name__ == "__main__":
    import sys
    sys.exit(main())
