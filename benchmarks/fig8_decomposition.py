"""Fig. 8: inference time decomposition — construction / scheduling /
execution — for the Cavs-DyNet proxy vs ED-Batch.

Two sources for the decomposition:

- the default mode re-runs the workloads with ``ExecStats`` timing fields
  (construction / scheduling / lowering / execution), as the paper does;
- ``--from-trace TRACE.json`` recomputes the same decomposition from a
  recorded serve trace (``--trace-out`` on the launcher or any benchmark)
  using per-span *self time* — a span's duration minus its direct
  children's — so nested phases (``plan.pack`` contains ``plan.schedule``
  and ``plan.lower``) are never double-counted.
"""

from __future__ import annotations

import argparse
import json
import random
import time

from repro.core.batching import best_baseline_schedule
from repro.core.executor import ExecStats
from repro.core.rl import RLConfig, train_fsm
from repro.models.workloads import make_workload

from .common import emit, make_executor

# Span-name -> Fig. 8 component mapping for --from-trace. Self time of the
# container spans (serve.run / serve.round / round.lm / round.single) is
# engine overhead and lands in "other".
COMPONENTS = {
    "schedule": ("round.schedule", "plan.schedule", "interp.schedule",
                 "round.lookup", "round.speculate", "round.spec_check",
                 "round.spec_snapshot"),
    "memory": ("round.pack", "plan.pack", "plan.lower", "plan.h2d",
               "round.scatter", "round.commit", "round.readback",
               "round.feed", "round.feed_stage"),
    "execution": ("round.dispatch", "plan.dispatch", "plan.block",
                  "interp.exec"),
    "compile": ("xla.compile", "jax.trace", "jax.compile"),
}


def span_self_times(events) -> list[dict]:
    """Complete spans annotated with ``self_us``: duration minus the summed
    durations of *direct* children (same tid, contained in time). Spans on
    one thread nest strictly (the tracer's stacks are thread-local), so a
    stack sweep over start-sorted spans recovers the hierarchy."""
    spans = [dict(e) for e in events if e.get("ph") == "X"]
    by_tid: dict = {}
    for s in spans:
        by_tid.setdefault(s.get("tid", 0), []).append(s)
    eps = 1e-3  # µs; guards against perf_counter quantization at the edges
    for ss in by_tid.values():
        # Parents start no later than their children and end no earlier;
        # ties broken by duration so the longer (outer) span comes first.
        ss.sort(key=lambda s: (s["ts"], -s["dur"]))
        stack: list[dict] = []
        for s in ss:
            s["_child_us"] = 0.0
            while stack and s["ts"] >= stack[-1]["ts"] + stack[-1]["dur"] - eps:
                stack.pop()
            if stack:
                stack[-1]["_child_us"] += s["dur"]
            stack.append(s)
    for s in spans:
        s["self_us"] = max(s["dur"] - s.pop("_child_us"), 0.0)
    return spans


def overlap_fraction(spans, names=("round.pack",)) -> float:
    """Fraction of the named spans' self time carrying the ``overlap``
    stamp — work the pipelined engine (DESIGN.md §9) performed while the
    previous round's dispatch was still in flight on the device, i.e. off
    the serve loop's critical path. 0.0 when the named spans never appear
    (serial engine, single-shot-only traces)."""
    tot = ov = 0.0
    for s in spans:
        if s["name"] in names:
            tot += s["self_us"]
            if s.get("args", {}).get("overlap"):
                ov += s["self_us"]
    return ov / tot if tot else 0.0


def decompose_trace(path: str) -> dict:
    """Fig. 8 components (ms of self time) from a Chrome trace-event file.

    Async compilation (DESIGN.md §8) moves lowering onto background worker
    threads; their spans (``plan.pack``/``plan.schedule``/``plan.lower``/
    ``xla.compile`` with ``args.bg``) are *not* serve-loop time, so they
    are totalled separately as ``compile_bg_ms`` and excluded from the
    on-loop components, the on-loop total, and the coverage ratio.
    Background spans are recognized by thread: any tid without a
    ``serve.run``/``serve.round`` span is a compile worker (plus the
    explicit ``args.bg`` stamp on ``xla.compile`` spans, which survives
    even single-threaded replays)."""
    with open(path) as f:
        obj = json.load(f)
    spans = span_self_times(obj["traceEvents"])
    name2comp = {n: c for c, names in COMPONENTS.items() for n in names}
    comp = {c: 0.0 for c in COMPONENTS}
    other = attributed = bg = overlapped = 0.0
    serve_tids = {s.get("tid", 0) for s in spans
                  if s["name"] in ("serve.run", "serve.round")}
    total_run = sum(s["dur"] for s in spans if s["name"] == "serve.run")
    for s in spans:
        if (s.get("args", {}).get("bg")
                or (serve_tids and s.get("tid", 0) not in serve_tids)):
            bg += s["self_us"]
            continue
        c = name2comp.get(s["name"])
        if c is not None:
            comp[c] += s["self_us"]
            attributed += s["self_us"]
            # Pipelined rounds (DESIGN.md §9) stamp speculative schedule/
            # pack spans with ``overlap``: that self time ran concurrently
            # with the in-flight device dispatch, so while it is still
            # attributed to its component above, it is NOT critical-path
            # latency — totalled here so the decomposition can report how
            # much host work the pipeline actually hid.
            if s.get("args", {}).get("overlap"):
                overlapped += s["self_us"]
        else:
            other += s["self_us"]
    out = {f"{c}_ms": v / 1e3 for c, v in comp.items()}
    out["other_ms"] = other / 1e3
    out["compile_bg_ms"] = bg / 1e3
    out["overlapped_ms"] = overlapped / 1e3
    out["pack_overlap_frac"] = overlap_fraction(
        [s for s in spans if not s.get("args", {}).get("bg")
         and (not serve_tids or s.get("tid", 0) in serve_tids)])
    out["total_ms"] = (attributed + other) / 1e3
    out["n_spans"] = len(spans)
    # Fraction of the serve loop's wall attributed to *named* component
    # spans — the >= 0.9 bar in the obs acceptance criteria. Traces without
    # a serve.run span (pure executor benches) report 0 coverage.
    out["coverage"] = attributed / total_run if total_run else 0.0
    return out


def run(workloads=("TreeLSTM", "LatticeLSTM"), batch_size: int = 16,
        model_size: int = 32, seed: int = 0, plan: str = "interpreted"):
    """``plan``: "interpreted", "compiled", or "both". The compiled rows add
    the one-time plan lowering+XLA-compile cost as its own component, and
    "both" emits the steady-state execution delta the plan layer buys."""
    plans = ("interpreted", "compiled") if plan == "both" else (plan,)
    rng = random.Random(seed)
    rows = []
    for name in workloads:
        for system, layout in (("cavs-dynet-proxy", "declaration"),
                               ("ed-batch", "planned")):
            wl = make_workload(name, model_size, seed, layout=layout)
            if system == "ed-batch":
                res = train_fsm([wl.sample_graph(rng, 2) for _ in range(3)],
                                RLConfig(max_iters=600, seed=seed))
                policy = res.policy
            else:
                policy = best_baseline_schedule
            # construction
            t0 = time.perf_counter()
            g = wl.sample_graph(rng, batch_size)
            t_construct = time.perf_counter() - t0
            exec_ms = {}
            for pl in plans:
                # warm, then measure schedule+exec separately (fresh caches
                # for scheduling time: use a fresh executor)
                make_executor(wl.impls, pl).run(g, policy)
                ex2 = make_executor(wl.impls, pl)
                stats = ExecStats()
                ex2.run(g, policy, stats)
                # execution steady-state (schedule/plan cached now)
                stats2 = ExecStats()
                ex2.run(g, policy, stats2)
                exec_ms[pl] = stats2.exec_time * 1e3
                emit(f"fig8/{name}/{system}/{pl}",
                     (t_construct + stats.schedule_time
                      + stats2.exec_time) * 1e6,
                     f"construct_ms={t_construct*1e3:.2f};"
                     f"schedule_ms={stats.schedule_time*1e3:.2f};"
                     f"lower_ms={stats.lower_time*1e3:.2f};"
                     f"exec_ms={stats2.exec_time*1e3:.2f};"
                     f"batches={stats2.n_batches};"
                     f"launches={stats2.n_launches}")
                rows.append((name, system, pl, t_construct,
                             stats.schedule_time, stats2.exec_time))
            if len(plans) == 2:
                emit(f"fig8/{name}/{system}/plan-delta", 0.0,
                     f"exec_speedup="
                     f"{exec_ms['interpreted'] / max(exec_ms['compiled'], 1e-9):.2f}x")
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--from-trace", default="", metavar="TRACE.json",
                    help="decompose a recorded Chrome trace (from "
                         "--trace-out) instead of re-running the workloads")
    ap.add_argument("--plan", default="interpreted",
                    choices=["interpreted", "compiled", "both"])
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--model-size", type=int, default=32)
    args = ap.parse_args(argv)
    if args.from_trace:
        d = decompose_trace(args.from_trace)
        emit("fig8/from-trace", d["total_ms"] * 1e3,
             ";".join(f"{k}={d[k]:.2f}" for k in
                      ("schedule_ms", "memory_ms", "execution_ms",
                       "compile_ms", "compile_bg_ms", "other_ms",
                       "overlapped_ms"))
             + f";pack_overlap={d['pack_overlap_frac']:.2f}"
             + f";coverage={d['coverage']:.2f};spans={d['n_spans']}")
        return 0
    run(batch_size=args.batch_size, model_size=args.model_size,
        plan=args.plan)
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
