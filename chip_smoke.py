"""Chip smoke test: the served path, end to end, on a TPU.

Drives ``ServeEngine`` the way ``python -m repro.launch.serve`` does with
its defaults (bucketed plans, continuous batching, async compile,
pipelined rounds) at ``model_size`` 512, the widest the serve path
documents and lane-aligned, so the Pallas gather and fused gather→cell
kernels are the ones the bucket programs select. In one process:

1. trains FSM batching policies for lm/tree/lattice into a registry under
   ``--out-dir`` (as ``--train-policy`` does), so the learned-policy path
   is what gets served;
2. serves a seeded mixed lm/tree/lattice trace: two warm-up passes, then
   a checked pass sharing the plan, pack and bucket caches. Every checked
   round must run on the primary (bucketed) tier, with no compile, no
   contained error, no quarantine and no failed request; the warm-up may
   serve degraded rounds while builds land, but none may time out;
3. serves the same trace through the interpreted reference engine
   (``compiled=False``) and compares: lm token streams equal, tree/lattice
   outputs within ``TOL``;
4. counts ``tpu_custom_call`` in every compiled bucket executable: each lm
   program must hold the Pallas kernels.

``--four-chips`` runs only the sharded replica path (``n_shards=4``, one
``shard_map`` dispatch per round) and its one-replica bucketed comparison.

The script refuses to run without a TPU. The last line of its output is
``{"ok": true, "device": {...}}``; any failed check exits non-zero.

    python chip_smoke.py
    python chip_smoke.py --four-chips
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

FAMILIES = ["lm", "tree", "lattice"]
MODEL_SIZE = 512
N_REQUESTS = 16
MAX_NEW = 16
RATE = 4.0          # arrivals per scheduler round (the launcher default)
MAX_SLOTS = 16      # the launcher default
SEED = 0
# Tree/lattice outputs against the interpreted reference. Every tier runs
# its matmuls at repro.core.ops.MATMUL_PRECISION (HIGHEST), so what is left
# is f32 summation order: XLA fusion vs Mosaic vs eager ops.
TOL = 1e-4
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def require_tpu(devices) -> dict:
    """The device stamp of the final line; exits non-zero off a TPU."""
    d = devices[0]
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke.py needs a TPU; jax found "
                         f"{d.platform!r} ({d.device_kind})")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


class CompileCounter:
    """Counts every executable jax builds or loads while ``armed``."""

    def __init__(self):
        self.armed = False
        self.names: list[str] = []

    def __call__(self, event: str, duration: float, **kwargs) -> None:
        if self.armed and event == BACKEND_COMPILE:
            self.names.append(str(kwargs.get("fun_name", "?")))


def serve(workloads, caches: dict, *, compiled: bool = True,
          n_shards: int = 1, registry=None, tracer=None):
    """One pass over the seeded trace; returns ``(requests, stats)``."""
    from repro.obs import Obs
    from repro.serve import ServeEngine, synth_trace
    from repro.serve.queue import COMPLETED

    reqs = synth_trace(FAMILIES, N_REQUESTS, RATE, MAX_NEW, workloads, SEED)
    eng = ServeEngine(workloads, compiled=compiled, max_slots=MAX_SLOTS,
                      model_size=MODEL_SIZE, seed=SEED, registry=registry,
                      n_shards=n_shards, async_compile=True,
                      obs=Obs(tracer=tracer) if tracer is not None else None,
                      **caches)
    eng.submit_many(reqs)
    try:
        stats = eng.run()
    finally:
        eng.close()
    for r in reqs:
        if r.status != COMPLETED:
            print(f"  request {r.rid} ({r.family}) {r.status}: {r.error}")
    return reqs, stats


def warm_and_check(workloads, counter: CompileCounter, **kw):
    """Two warm-up passes, then a checked pass, all on the same caches.
    The first warm-up builds the bucket executables (in the background,
    serving degraded rounds until they land); the second runs every round
    on the primary tier, which builds the small per-shape jits of the
    commit and result paths. Returns the checked pass, the first warm-up's
    stats and compile spans, the names of executables built inside the
    checked pass, and the bucket cache."""
    from repro.core.cache import FIFOCache, LRUCache
    from repro.obs import Tracer

    caches = dict(plan_cache=FIFOCache(64), schedule_cache=FIFOCache(512),
                  bucket_cache=LRUCache(32))
    tracer = Tracer(enabled=True)
    _, warm = serve(workloads, caches, tracer=tracer, **kw)
    serve(workloads, caches, **kw)
    counter.names, counter.armed = [], True
    try:
        reqs, stats = serve(workloads, caches, **kw)
    finally:
        counter.armed = False
    return (reqs, stats, warm, tracer.spans("xla.compile"), counter.names,
            caches["bucket_cache"])


def compare(reqs, ref_reqs) -> tuple[bool, bool, float]:
    """``(lm tokens equal, all outputs bit-identical, max |diff|)`` over
    position-aligned requests of two passes of the same trace."""
    lm_equal, identical, worst = True, True, 0.0
    for a, b in zip(reqs, ref_reqs, strict=True):
        if a.family == "lm":
            lm_equal &= a.out == b.out
            continue
        x, y = np.asarray(a.result), np.asarray(b.result)
        if x.shape != y.shape:
            return lm_equal, False, float("inf")
        identical &= bool(np.array_equal(x, y))
        worst = max(worst, float(np.max(np.abs(x - y))) if x.size else 0.0)
    return lm_equal, identical and lm_equal, worst


def kernel_counts(bucket_cache) -> tuple[dict, dict]:
    """``tpu_custom_call`` occurrences and ``memory_analysis()`` of each
    cached bucket executable, grouped by family (the executor namespace is
    ``(family, id(impls))``)."""
    counts: dict[str, list[int]] = {}
    memory: dict[str, list[dict]] = {}
    for key, (exe, _pool, _impls) in list(bucket_cache.items()):
        fam = key[0][0]
        counts.setdefault(fam, []).append(
            exe.as_text().count("tpu_custom_call"))
        m = exe.memory_analysis()
        memory.setdefault(fam, []).append(
            {k: getattr(m, k) for k in ("argument_size_in_bytes",
                                        "output_size_in_bytes",
                                        "temp_size_in_bytes",
                                        "generated_code_size_in_bytes")})
    return counts, memory


def summarize(name: str, stats) -> dict:
    keys = ("n_rounds", "n_compiles", "tokens_out", "outputs_out",
            "requests_done", "requests_failed", "requests_timed_out",
            "requests_rejected", "n_contained_errors", "n_quarantine_events",
            "compile_jobs_submitted", "compile_jobs_landed",
            "compile_jobs_retried", "compile_jobs_timed_out",
            "compile_jobs_quarantined", "n_hotswaps", "n_pipelined_rounds",
            "n_sharded_dispatches", "n_shard_fallback_rounds", "wall_s",
            "lower_s", "lower_bg_s")
    d = {k: getattr(stats, k) for k in keys}
    d["tier_rounds"] = dict(stats.tier_rounds)
    print(f"{name}: {json.dumps(d, sort_keys=True)}")
    return d


def check_pass(name: str, stats, built: list[str], tier: str,
               failures: list[str]) -> None:
    """The checked pass ran every round on ``tier`` and paid for nothing."""
    if set(stats.tier_rounds) != {tier}:
        failures.append(f"{name}: rounds off the {tier} tier: "
                        f"{stats.tier_rounds}")
    for field in ("n_compiles", "n_contained_errors", "n_quarantine_events",
                  "compile_jobs_submitted", "compile_jobs_timed_out",
                  "compile_jobs_quarantined", "requests_failed",
                  "requests_timed_out", "requests_rejected"):
        if getattr(stats, field):
            failures.append(f"{name}: {field} = {getattr(stats, field)}")
    if built:
        failures.append(f"{name}: {len(built)} executable(s) built or "
                        f"loaded inside the checked pass: {built}")
    if stats.requests_done != N_REQUESTS:
        failures.append(f"{name}: {stats.requests_done}/{N_REQUESTS} "
                        f"requests done")


def check_warmup(name: str, stats, failures: list[str]) -> None:
    """Warm-up may serve degraded rounds while builds land, but no build
    may time out or be quarantined and nothing may be contained."""
    for field in ("n_contained_errors", "n_quarantine_events",
                  "compile_jobs_timed_out", "compile_jobs_quarantined",
                  "requests_failed"):
        if getattr(stats, field):
            failures.append(f"{name}: {field} = {getattr(stats, field)}")


def compile_report(spans) -> dict:
    secs = sorted(s["args"].get("lower_s", s["dur"] / 1e6) for s in spans)
    rep = {"n": len(secs), "max_s": secs[-1] if secs else 0.0,
           "total_s": sum(secs), "all_s": secs}
    print(f"compile times (s): {json.dumps(rep)}")
    return rep


def run_one_chip(out_dir: str, counter: CompileCounter) -> tuple[dict, list]:
    from repro.launch.serve import train_policies
    from repro.models.workloads import SERVE_FAMILIES, make_workload
    from repro.serve import PolicyRegistry

    failures: list[str] = []
    workloads = {f: make_workload(SERVE_FAMILIES[f], MODEL_SIZE, SEED)
                 for f in FAMILIES}
    reg_dir = os.path.join(out_dir, "registry")
    shutil.rmtree(reg_dir, ignore_errors=True)
    registry = PolicyRegistry(reg_dir)
    train_policies(registry, FAMILIES, workloads, SEED)
    for fam in FAMILIES:
        if registry.auto_select(fam) is None:
            failures.append(f"no learned {fam} policy in {reg_dir}")

    reqs, stats, warm, spans, built, bucket_cache = warm_and_check(
        workloads, counter, registry=registry)
    report = {"warmup": summarize("warm-up pass", warm),
              "checked": summarize("checked pass", stats),
              "compile": compile_report(spans),
              "checked_pass_builds": built}
    check_warmup("warm-up pass", warm, failures)
    check_pass("checked pass", stats, built, "bucketed", failures)

    ref_reqs, ref_stats = serve(workloads, {}, compiled=False,
                                registry=registry)
    report["reference"] = summarize("interpreted reference", ref_stats)
    lm_equal, identical, worst = compare(reqs, ref_reqs)
    report.update(lm_tokens_equal=lm_equal, bit_identical=identical,
                  max_abs_diff=worst, tol=TOL)
    print(f"vs interpreted reference: lm tokens equal {lm_equal}, "
          f"bit-identical {identical}, max |diff| tree/lattice {worst!r} "
          f"(tol {TOL})")
    if not lm_equal:
        failures.append("lm token streams differ from the reference")
    if not worst <= TOL:
        failures.append(f"tree/lattice max |diff| {worst!r} > {TOL}")

    kc, mem = kernel_counts(bucket_cache)
    report.update(tpu_custom_call=kc, memory_analysis=mem)
    print(f"tpu_custom_call per bucket executable: {json.dumps(kc)}")
    print(f"lm bucket memory_analysis: {json.dumps(mem.get('lm'))}")
    if not kc.get("lm") or min(kc["lm"]) == 0:
        failures.append(f"an lm bucket executable holds no Pallas kernel: "
                        f"{kc}")
    return report, failures


def run_four_chips(counter: CompileCounter) -> tuple[dict, list]:
    from repro.models.workloads import SERVE_FAMILIES, make_workload

    failures: list[str] = []
    if len(jax.devices()) < 4:
        return {}, [f"--four-chips needs 4 devices, jax sees "
                    f"{len(jax.devices())}"]
    workloads = {f: make_workload(SERVE_FAMILIES[f], MODEL_SIZE, SEED)
                 for f in FAMILIES}
    one, one_stats, one_warm, _, n1, _ = warm_and_check(workloads, counter)
    four, stats, warm, spans, n4, _ = warm_and_check(workloads, counter,
                                                     n_shards=4)
    report = {"one_replica": summarize("one replica, checked", one_stats),
              "sharded_warmup": summarize("4 replicas, warm-up", warm),
              "sharded": summarize("4 replicas, checked", stats),
              "compile": compile_report(spans)}
    check_warmup("one replica warm-up", one_warm, failures)
    check_pass("one replica", one_stats, n1, "bucketed", failures)
    check_warmup("4 replicas warm-up", warm, failures)
    check_pass("4 replicas", stats, n4, "sharded", failures)
    if stats.n_shard_fallback_rounds:
        failures.append(f"{stats.n_shard_fallback_rounds} sharded rounds "
                        f"fell back to per-shard dispatch")
    lm_equal, identical, worst = compare(four, one)
    report.update(lm_tokens_equal=lm_equal, bit_identical=identical,
                  max_abs_diff=worst, tol=TOL,
                  n_sharded_dispatches=stats.n_sharded_dispatches)
    print(f"4 replicas vs 1: outputs bit-identical {identical}, lm tokens "
          f"equal {lm_equal}, max |diff| {worst!r}; "
          f"{stats.n_sharded_dispatches} sharded dispatches over "
          f"{stats.n_rounds} rounds, {stats.n_shard_fallback_rounds} "
          f"fallback rounds")
    if not lm_equal:
        failures.append("sharded lm token streams differ from one replica")
    if not worst <= TOL:
        failures.append(f"sharded max |diff| {worst!r} > {TOL}")
    return report, failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-replica sharded path and its "
                         "one-replica comparison (needs 4 chips)")
    ap.add_argument("--out-dir",
                    default=os.path.join(ROOT, "chiprun_out", "chip_smoke"),
                    help="policy registry and report.json go here")
    args = ap.parse_args(argv)

    device = require_tpu(jax.devices())
    from repro.launch.jaxcache import enable_compilation_cache
    enable_compilation_cache()
    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    os.makedirs(args.out_dir, exist_ok=True)

    if args.four_chips:
        report, failures = run_four_chips(counter)
    else:
        report, failures = run_one_chip(args.out_dir, counter)
    report.update(device=device, failures=failures)
    with open(os.path.join(args.out_dir, "report.json"), "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
    for msg in failures:
        print(f"FAIL: {msg}", file=sys.stderr)
    if failures:
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
