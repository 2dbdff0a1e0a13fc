"""Perf smoke: interpreted vs compiled-plan execution on the quickstart
chain workload. Writes ``BENCH_plan.json`` so CI records the perf trajectory.

    PYTHONPATH=src python -m benchmarks.bench_plan [--out BENCH_plan.json]

The compiled plan must hold a >= 2x end-to-end speedup here (one device
dispatch + contiguous arena slices vs one dispatch, gather and scatter per
batch) — the acceptance bar for the plan-compilation layer.
"""

from __future__ import annotations

import argparse
import json
import random

from repro.core.batching import SufficientConditionPolicy
from repro.core.executor import DynamicExecutor, ExecStats
from repro.core.plan import PlanExecutor
from repro.models.workloads import make_workload
from repro.launch.jaxcache import enable_compilation_cache

from .common import (add_obs_args, emit, maybe_enable_obs, platform_payload,
                     timeit, write_obs)


def run(out: str = "", model_size: int = 64, batch_size: int = 16,
        seed: int = 0, donate: bool = True) -> dict:
    rng = random.Random(seed)
    wl = make_workload("BiLSTM-Tagger", model_size, seed, layout="planned")
    g = wl.sample_graph(rng, batch_size)
    policy = SufficientConditionPolicy()

    interp = DynamicExecutor(wl.impls, None)
    compiled = PlanExecutor(wl.impls, None, donate=donate)

    t_interp = timeit(lambda: interp.run(g, policy), warmup=2, iters=7)
    t_comp = timeit(lambda: compiled.run(g, policy), warmup=2, iters=7)

    stats_i, stats_c = ExecStats(), ExecStats()
    interp.run(g, policy, stats_i)
    compiled.run(g, policy, stats_c)
    plan = compiled.plan_for(g, policy)

    n_batches = stats_i.n_batches
    result = {
        **platform_payload(),
        "workload": "BiLSTM-Tagger (quickstart chain)",
        "model_size": model_size,
        "batch_size": batch_size,
        "graph_nodes": len(g),
        "n_batches": n_batches,
        "interpreted_s_per_run": t_interp,
        "compiled_s_per_run": t_comp,
        "interpreted_batches_per_s": n_batches / t_interp,
        "compiled_batches_per_s": n_batches / t_comp,
        "speedup": t_interp / t_comp,
        "interpreted_launches_per_run": stats_i.n_launches,
        "compiled_launches_per_run": stats_c.n_launches,
        "plan_stats": plan.stats.as_dict(),
    }
    emit("bench_plan/interpreted", t_interp * 1e6,
         f"batches_per_s={result['interpreted_batches_per_s']:.1f}")
    emit("bench_plan/compiled", t_comp * 1e6,
         f"batches_per_s={result['compiled_batches_per_s']:.1f};"
         f"speedup={result['speedup']:.2f}x;"
         f"gather_fallback_steps={plan.stats.n_gather_fallback_steps}")
    if out:
        with open(out, "w") as f:
            json.dump(result, f, indent=1)
        print(f"# wrote {out}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_plan.json")
    ap.add_argument("--model-size", type=int, default=64)
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--no-donate", action="store_true",
                    help="disable arena donation (allocation per run)")
    add_obs_args(ap)
    args = ap.parse_args(argv)
    enable_compilation_cache()
    maybe_enable_obs(args)
    res = run(out=args.out, model_size=args.model_size,
              batch_size=args.batch_size, donate=not args.no_donate)
    write_obs(args)
    return 0 if res["speedup"] >= 2.0 else 1  # the documented acceptance bar


if __name__ == "__main__":
    import sys
    sys.exit(main())
