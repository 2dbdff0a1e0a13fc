"""Goodput-under-faults benchmark: the fault-isolation acceptance gate.

The same synthetic trace (lm + tree + lattice, poisson and burst arrival
processes, generous per-request deadlines) is served twice on the bucketed
compiled path: a **clean** run, then a **faulted** run under the standard
fault mix — injected compile failures, injected executor exceptions,
an injected slow round, and poisoned (semantically malformed) request
graphs. Everything injected is deterministic (``serve/faults.py``), and the
engine's clock is virtual, so the gates below are reproducible.

Acceptance (checked here, recorded in ``BENCH_faults.json``, and gated in
CI's fault-smoke job):

- **zero engine crashes**: ``ServeEngine.run`` returns normally in every
  configuration — faults degrade rounds and fail requests, never the loop;
- **every request terminal**: each request ends in exactly one of
  ``COMPLETED`` / ``FAILED`` / ``TIMED_OUT`` / ``REJECTED``, and the
  poisoned requests are the ``FAILED`` ones (``BAD_TOPOLOGY``);
- **healthy outputs match the clean run**: lm token streams are exactly
  equal (decode lanes are independent, so tier degradation cannot change
  them); single-shot logits match to 1e-4 (the interpreted floor and the
  bucketed program associate reductions differently) — strict bitwise
  equality is recorded separately as ``single_shot_bitwise``;
- **request goodput >= 90% of clean**: the faulted run completes at least
  90% of the healthy requests the clean run completes.

    PYTHONPATH=src python -m benchmarks.bench_faults [--out BENCH_faults.json]
"""

from __future__ import annotations

import argparse
import json

import numpy as np

from repro.models.workloads import SERVE_FAMILIES, make_workload
from repro.serve import ServeEngine, synth_trace
from repro.serve.faults import FaultInjector, poison_requests
from repro.serve.queue import COMPLETED, FAILED, TERMINAL
from repro.launch.jaxcache import enable_compilation_cache

from .common import (add_obs_args, emit, maybe_enable_obs, platform_payload,
                     write_obs)

FAMILIES = ["lm", "tree", "lattice"]

# The standard fault mix: two failed compiles (quarantine + interpreted
# degradation, then recovery), executor exceptions at two rounds, one slow
# round burning virtual time against the deadlines, three malformed
# topologies. Deadlines are generous (clean traffic finishes far inside
# them) so the goodput gate measures fault isolation, not SLO pressure.
FAULT_SPEC = "compile_fail=2,exec_rounds=2:5,slow=4*3.0,poison=3"
DEADLINE = 500.0


def fault_trace(workloads, n, rate, max_new, seed, arrivals):
    reqs = synth_trace(FAMILIES, n, rate, max_new, workloads, seed,
                       arrivals=arrivals)
    for r in reqs:
        r.deadline = r.arrival + DEADLINE
    return reqs


def serve_once(workloads, reqs, *, max_slots, injector=None):
    eng = ServeEngine(dict(workloads), compiled=True, bucketed=True,
                      continuous=True, max_slots=max_slots,
                      fault_injector=injector)
    eng.submit_many(reqs)
    try:
        stats = eng.run()
    except Exception as exc:                      # the no-crash gate
        return None, f"{type(exc).__name__}: {exc}"
    return stats, None


def healthy_match(faulted, clean):
    """Compare the faulted run's completed healthy requests against the
    clean run, index-aligned (same seed => same request contents).
    Returns (exact_lm, close_single, bitwise_single)."""
    exact_lm = close_single = bitwise_single = True
    for a, b in zip(faulted, clean):
        if a.status != COMPLETED or b.status != COMPLETED:
            continue
        if a.family == "lm":
            exact_lm = exact_lm and a.out == b.out
        else:
            close_single = close_single and np.allclose(
                a.result, b.result, rtol=1e-4, atol=1e-5)
            bitwise_single = bitwise_single and bool(
                np.array_equal(a.result, b.result))
    return exact_lm, close_single, bitwise_single


def run(out: str = "", model_size: int = 16, requests: int = 16,
        rate: float = 2.0, max_new: int = 4, max_slots: int = 8,
        seed: int = 0, arrivals_list: tuple[str, ...] = ("poisson", "burst"),
        ) -> dict:
    workloads = {f: make_workload(SERVE_FAMILIES[f], model_size, seed)
                 for f in FAMILIES}
    result: dict = {"model_size": model_size,
                    "requests": requests, "rate": rate, "max_new": max_new,
                    "max_slots": max_slots, "fault_spec": FAULT_SPEC,
                    "deadline": DEADLINE}
    all_ok = True

    for arrivals in arrivals_list:
        clean_reqs = fault_trace(workloads, requests, rate, max_new, seed,
                                 arrivals)
        clean_stats, clean_crash = serve_once(workloads, clean_reqs,
                                              max_slots=max_slots)

        injector = FaultInjector.from_spec(FAULT_SPEC)
        faulted_reqs = fault_trace(workloads, requests, rate, max_new, seed,
                                   arrivals)
        poisoned = poison_requests(injector.poison, arrival=1.0)
        faulted_stats, fault_crash = serve_once(
            workloads, faulted_reqs + poisoned, max_slots=max_slots,
            injector=injector)

        crashed = clean_crash is not None or fault_crash is not None
        entry: dict = {"crashed": crashed,
                       "crash": clean_crash or fault_crash}
        if not crashed:
            all_terminal = all(r.status in TERMINAL
                               for r in faulted_reqs + poisoned)
            poison_failed = all(
                r.status == FAILED
                and r.error["code"] == "BAD_TOPOLOGY" for r in poisoned)
            exact_lm, close_single, bitwise_single = healthy_match(
                faulted_reqs, clean_reqs)
            clean_done = sum(r.status == COMPLETED for r in clean_reqs)
            fault_done = sum(r.status == COMPLETED for r in faulted_reqs)
            goodput = fault_done / max(clean_done, 1)
            entry.update({
                "all_terminal": all_terminal,
                "poison_failed": poison_failed,
                "lm_tokens_exact": exact_lm,
                "single_shot_close": close_single,
                "single_shot_bitwise": bitwise_single,
                "clean_completed": clean_done,
                "faulted_completed": fault_done,
                "goodput_ratio": goodput,
                "clean": clean_stats.as_dict(),
                "faulted": faulted_stats.as_dict(),
            })
            ok = (all_terminal and poison_failed and exact_lm
                  and close_single and goodput >= 0.9)
        else:
            ok = False
        entry["ok"] = ok
        all_ok = all_ok and ok
        result[arrivals] = entry
        if not crashed:
            emit(f"bench_faults/{arrivals}", faulted_stats.wall_s * 1e6,
                 f"goodput={entry['goodput_ratio']:.2f};"
                 f"contained={faulted_stats.n_contained_errors};"
                 f"quarantine={faulted_stats.n_quarantine_events};"
                 f"tiers={'+'.join(sorted(faulted_stats.tier_rounds))};"
                 f"ok={ok}")
        else:
            emit(f"bench_faults/{arrivals}", 0.0,
                 f"CRASHED:{entry['crash']}")

    result["ok"] = all_ok
    # Stamped after the measured phases so the obs_metrics snapshot carries
    # the run's counters, not an empty registry.
    result.update(platform_payload())
    if out:
        with open(out, "w") as f:
            json.dump(result, f, indent=1)
        print(f"# wrote {out}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_faults.json")
    ap.add_argument("--model-size", type=int, default=16)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=2.0)
    ap.add_argument("--max-new", type=int, default=4)
    ap.add_argument("--max-slots", type=int, default=8)
    add_obs_args(ap)
    args = ap.parse_args(argv)
    enable_compilation_cache()
    maybe_enable_obs(args)
    res = run(out=args.out, model_size=args.model_size,
              requests=args.requests, rate=args.rate, max_new=args.max_new,
              max_slots=args.max_slots)
    write_obs(args)
    # CI gate (fault-smoke): no engine crash anywhere, every request in a
    # terminal state, poisoned topologies contained as BAD_TOPOLOGY
    # failures, healthy outputs matching the clean run, and >= 90% of
    # clean-request goodput under the standard fault mix.
    return 0 if res["ok"] else 1


if __name__ == "__main__":
    import sys
    sys.exit(main())
