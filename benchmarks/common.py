"""Shared benchmark helpers. Every benchmark prints ``name,us_per_call,derived``
CSV rows (one per measured configuration)."""

from __future__ import annotations

import json
import time

from repro.obs.metrics import default_registry, latency_summary, percentile

__all__ = ["add_obs_args", "maybe_enable_obs", "write_obs", "platform_payload",
           "timeit", "emit", "make_executor", "percentile", "latency_summary"]


def add_obs_args(ap) -> None:
    """`--trace-out` / `--metrics-out`: observability exports (DESIGN.md §6).

    The flags light up the *process-default* tracer/registry, which every
    engine and executor falls back to when not handed an explicit ``Obs`` —
    so one flag traces the whole benchmark without plumbing changes.
    """
    ap.add_argument("--trace-out", default="",
                    help="write a Chrome/Perfetto trace-event JSON of the "
                         "whole benchmark run here")
    ap.add_argument("--metrics-out", default="",
                    help="write a metrics-registry snapshot JSON here")


def maybe_enable_obs(args) -> None:
    """Enable the process-default tracer when a trace export was requested.
    Call before any engine work so spans from the first round on are kept."""
    if getattr(args, "trace_out", ""):
        from repro.obs.tracer import default_tracer
        default_tracer().enabled = True


def write_obs(args) -> None:
    """Export the requested observability artifacts (call after the run)."""
    if getattr(args, "trace_out", ""):
        from repro.obs.tracer import default_tracer
        default_tracer().write(args.trace_out)
        print(f"# wrote {args.trace_out}")
    if getattr(args, "metrics_out", ""):
        with open(args.metrics_out, "w") as f:
            json.dump(default_registry().snapshot(), f, indent=1)
        print(f"# wrote {args.metrics_out}")


def platform_payload(mesh=None) -> dict:
    """Execution-environment stamp for every BENCH_*.json payload: jax
    platform, device count, the mesh shape (empty when unsharded), and a
    snapshot of the process-default metrics registry — call it when the
    measured work is done so the snapshot carries the run's counters."""
    import jax

    from repro.launch.env import active_profile

    return {"jax_platform": jax.default_backend(),
            "jax_device_count": jax.device_count(),
            "mesh_shape": dict(mesh.shape) if mesh is not None else {},
            "perf_profile": active_profile(),
            "obs_metrics": default_registry().snapshot()}


def timeit(fn, *, warmup: int = 2, iters: int = 5) -> float:
    """Median wall time per call in seconds."""
    for _ in range(warmup):
        fn()
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return percentile(times, 50)


def emit(name: str, us_per_call: float, derived: str = "") -> None:
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


def make_executor(impls, plan: str):
    """The --plan axis: "interpreted" -> reference DynamicExecutor,
    "compiled" -> single-dispatch PlanExecutor."""
    from repro.core.executor import DynamicExecutor
    from repro.core.plan import PlanExecutor

    if plan == "compiled":
        return PlanExecutor(impls, None)
    if plan != "interpreted":
        raise ValueError(f"unknown plan mode {plan!r}")
    return DynamicExecutor(impls, None)
