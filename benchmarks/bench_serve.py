"""Serve smoke: continuous batching on compiled plans vs the wave-by-wave
interpreted loop. Writes ``BENCH_serve.json`` so CI records the trajectory.

Two measurements on the same traffic:

- **LM trace** — staggered chain-LM generation requests. The baseline
  drains wave-by-wave through the interpreted executor (the old
  ``serve/engine.py`` discipline); the subsystem folds arrivals into
  in-flight decode waves and dispatches one compiled plan per round.
  Acceptance bar: >= 2x tokens/s (after a warmup pass so both sides run
  from warm schedule/plan/jit caches — steady-state serving, not compile
  time, is what a long-running server sees). Note the bucketed default
  trades round-count TTFT for compile-robustness: prefills feed one prompt
  token per round, so first output lands ~bucket_len(prompt) rounds after
  admission; per-round TTFT percentiles are in the JSON, and
  ``bench_churn.py`` gates the wall-clock side where that trade pays off.
- **Mixed trace** — tree + lattice request mixes served through the
  compiled path and equivalence-checked against the interpreted reference
  executor (exact same outputs required).

    PYTHONPATH=src python -m benchmarks.bench_serve [--out BENCH_serve.json]
"""

from __future__ import annotations

import argparse
import json

import numpy as np

from repro.core.cache import FIFOCache, LRUCache
from repro.models.workloads import make_workload
from repro.serve import ServeEngine, synth_trace
from repro.launch.jaxcache import enable_compilation_cache

from .common import (add_obs_args, emit, maybe_enable_obs, platform_payload,
                     write_obs)


def lm_trace(workloads, n, rate, max_new, seed=0):
    # narrow prompt range: recurring prefill-bucket shapes, fewer topologies
    return synth_trace(["lm"], n, rate, max_new, workloads, seed,
                       prompt_lo=5, prompt_hi=8)


def mixed_trace(workloads, n, rate, seed=0):
    return synth_trace(["tree", "lattice"], n, rate, 0, workloads, seed,
                       tree_leaves=(4, 7), lattice_chars=(5, 9))


def serve_pass(workloads, reqs, *, compiled, continuous, max_slots,
               plan_cache=None, schedule_cache=None, bucket_cache=None):
    eng = ServeEngine(workloads, compiled=compiled, continuous=continuous,
                      max_slots=max_slots, plan_cache=plan_cache,
                      schedule_cache=schedule_cache,
                      bucket_cache=bucket_cache)
    eng.submit_many(reqs)
    stats = eng.run()
    return reqs, stats


def run(out: str = "", model_size: int = 32, requests: int = 32,
        max_new: int = 20, rate: float = 4.0, max_slots: int = 32,
        seed: int = 0) -> dict:
    workloads = {"lm": make_workload("ChainLM", model_size, seed),
                 "tree": make_workload("TreeLSTM", model_size, seed),
                 "lattice": make_workload("LatticeLSTM", model_size, seed)}

    # -- LM trace: wave+interpreted baseline vs continuous+compiled --------
    modes = {"interpreted_wave": dict(compiled=False, continuous=False),
             "compiled_continuous": dict(compiled=True, continuous=True)}
    lm_stats = {}
    for name, kw in modes.items():
        plan_cache, sched_cache = FIFOCache(64), FIFOCache(512)
        bucket_cache = LRUCache(32)
        for timed in (False, True):   # warmup pass, then measured pass
            reqs = lm_trace(workloads, requests, rate, max_new, seed)
            _, stats = serve_pass(workloads, reqs, max_slots=max_slots,
                                  plan_cache=plan_cache,
                                  schedule_cache=sched_cache,
                                  bucket_cache=bucket_cache, **kw)
        lm_stats[name] = stats
        emit(f"bench_serve/{name}", stats.wall_s * 1e6,
             f"tok_per_s={stats.tok_per_s:.1f};rounds={stats.n_rounds};"
             f"launches={stats.n_launches}")

    speedup = (lm_stats["compiled_continuous"].tok_per_s /
               max(lm_stats["interpreted_wave"].tok_per_s, 1e-9))

    # -- mixed tree+lattice trace: compiled path vs reference executor -----
    mix_outputs = {}
    for name, compiled in (("interpreted", False), ("compiled", True)):
        reqs = mixed_trace(workloads, 8, rate, seed)
        reqs, stats = serve_pass(workloads, reqs, compiled=compiled,
                                 continuous=True, max_slots=max_slots)
        mix_outputs[name] = [np.asarray(r.result) for r in reqs]
    mix_equivalent = all(
        a.shape == b.shape and np.allclose(a, b, rtol=1e-4, atol=1e-4)
        for a, b in zip(mix_outputs["interpreted"], mix_outputs["compiled"]))
    emit("bench_serve/mixed_equivalence", 0.0, f"equal={mix_equivalent}")

    result = {
        **platform_payload(),
        "model_size": model_size, "requests": requests, "max_new": max_new,
        "rate": rate, "max_slots": max_slots,
        "interpreted_wave": lm_stats["interpreted_wave"].as_dict(),
        "compiled_continuous": lm_stats["compiled_continuous"].as_dict(),
        "speedup_tok_per_s": speedup,
        "mixed_trace_equivalent": bool(mix_equivalent),
    }
    emit("bench_serve/speedup", 0.0, f"speedup={speedup:.2f}x")
    if out:
        with open(out, "w") as f:
            json.dump(result, f, indent=1)
        print(f"# wrote {out}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_serve.json")
    ap.add_argument("--model-size", type=int, default=32)
    # Sized so the steady-state ratio has margin over its 2x bar: the
    # token-level feed path spends one round per (padded) prompt token, a
    # fixed cost that longer decode phases amortize — and a longer measured
    # pass keeps shared-runner timing noise out of the gate.
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=20)
    ap.add_argument("--rate", type=float, default=4.0)
    add_obs_args(ap)
    args = ap.parse_args(argv)
    enable_compilation_cache()
    maybe_enable_obs(args)
    res = run(out=args.out, model_size=args.model_size,
              requests=args.requests, max_new=args.max_new, rate=args.rate)
    write_obs(args)
    ok = res["speedup_tok_per_s"] >= 2.0 and res["mixed_trace_equivalent"]
    return 0 if ok else 1   # the documented acceptance bar


if __name__ == "__main__":
    import sys
    sys.exit(main())
