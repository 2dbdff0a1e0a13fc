"""Host spans on the profiler's clock, and the reduction of a device trace.

- :class:`AnnotatingTracer` is the program's own ``repro.obs`` tracer with
  one addition: every span also opens a ``jax.profiler.TraceAnnotation`` of
  the same name, so the profiler's trace shows what the host was doing in
  each idle gap of the device.
- :func:`read_xplane` flattens the ``.xplane.pb`` the profiler wrote into
  plain event dicts; :func:`reduce_trace` turns those into the device's
  busy seconds, the operations that took most time, and the idle gaps by
  host span. The reduction works on the plain dicts, so a test can feed it
  a small recorded trace.
- :func:`span_self_times` is the self-time arithmetic of
  ``benchmarks/fig8_decomposition.py``, copied so the yardstick stays here.
"""

from __future__ import annotations

import glob
import os

from repro.obs.tracer import NULL_SPAN, Tracer

WINDOW = "bench.window"
OP_LINES = ("XLA Ops",)          # per-operation lines of a device plane
HOST_OUTSIDE = "harness"         # a gap no program span covers


class _Both:
    __slots__ = ("span", "ann")

    def __init__(self, span, ann):
        self.span = span
        self.ann = ann

    def __enter__(self):
        self.ann.__enter__()
        self.span.__enter__()
        return self.span

    def __exit__(self, *exc):
        self.span.__exit__(*exc)
        self.ann.__exit__(*exc)
        return False


class AnnotatingTracer(Tracer):
    """A ``repro.obs`` tracer whose spans also land in the profiler trace."""

    def span(self, name: str, cat: str = "serve", **args):
        if not self.enabled:
            return NULL_SPAN
        import jax

        return _Both(super().span(name, cat, **args),
                     jax.profiler.TraceAnnotation(name))


def profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # no per-call Python events
    opts.host_tracer_level = 2        # keeps TraceAnnotation spans
    return opts


def read_xplane(trace_dir: str) -> list[dict]:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir``."""
    import jax

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(paths[-1])
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                out.append({"plane": plane.name, "line": line.name,
                            "name": ev.name, "ts": float(ev.start_ns),
                            "dur": float(ev.duration_ns)})
    return out


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _innermost(spans: list[tuple[float, float, str]], t0: float, t1: float
               ) -> list[tuple[float, float, str]]:
    """Cut ``[t0, t1]`` into segments, each named by the innermost of the
    properly nested spans that covers it (``HOST_OUTSIDE`` where none
    does)."""
    bounds = []
    for i, (ts, dur, _) in enumerate(spans):
        bounds.append((ts, 1, -dur, i))
        bounds.append((ts + dur, 0, 0.0, i))
    bounds.sort()
    segs, stack, t = [], [], t0
    for time, kind, _, i in bounds:
        time = min(max(time, t0), t1)
        if time > t:
            segs.append((t, time, spans[stack[-1]][2] if stack
                         else HOST_OUTSIDE))
            t = time
        if kind:
            stack.append(i)
        elif i in stack:
            stack.remove(i)
    if t1 > t:
        segs.append((t, t1, HOST_OUTSIDE))
    return segs


def _attribute(gaps: list[tuple[float, float]],
               segs: list[tuple[float, float, str]]) -> dict[str, float]:
    """Time of each (sorted, disjoint) gap, split by the segments' names."""
    out: dict[str, float] = {}
    j = 0
    for a, b in gaps:
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < b:
            lo, hi = max(a, segs[k][0]), min(b, segs[k][1])
            if hi > lo:
                out[segs[k][2]] = out.get(segs[k][2], 0.0) + (hi - lo)
            k += 1
    return out


def reduce_trace(events: list[dict], top: int = 10) -> dict | None:
    """Device busy time, top operations and idle gaps inside the window.

    The window is the ``bench.window`` annotation. Busy time is the union
    of the operation intervals of each device plane that ran anything in
    the window, averaged over those planes. The time of each gap between
    operations goes to the innermost host annotation, on the thread that
    holds the window, that covers it, instant by instant. Returns ``None``
    when the trace holds no window or no device operation."""
    win = [e for e in events if e["name"] == WINDOW]
    if not win:
        return None
    w = max(win, key=lambda e: e["dur"])
    w0, w1 = w["ts"], w["ts"] + w["dur"]
    per_dev: dict[str, list[tuple[float, float]]] = {}
    op_time: dict[str, float] = {}
    for e in events:
        if not (e["plane"].startswith("/device:") and e["line"] in OP_LINES):
            continue
        a, b = max(e["ts"], w0), min(e["ts"] + e["dur"], w1)
        if b <= a:
            continue
        per_dev.setdefault(e["plane"], []).append((a, b))
        op_time[e["name"]] = op_time.get(e["name"], 0.0) + (b - a)
    if not per_dev:
        return None
    busy = {p: sum(b - a for a, b in _union(iv)) for p, iv in per_dev.items()}
    first = sorted(per_dev)[0]
    gaps, prev = [], w0
    for a, b in _union(per_dev[first]):
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    if w1 > prev:
        gaps.append((prev, w1))
    host = [(e["ts"], e["dur"], e["name"]) for e in events
            if e["plane"] == w["plane"] and e["line"] == w["line"]
            and e["name"] != WINDOW and e["ts"] < w1
            and e["ts"] + e["dur"] > w0]
    idle = _attribute(gaps, _innermost(host, w0, w1))
    ns = 1e-9
    return {
        "window_s": (w1 - w0) * ns,
        "busy_s": sum(busy.values()) / len(busy) * ns,
        "n_devices": len(busy),
        "device_ops": [[k, v * ns] for k, v in
                       sorted(op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v * ns] for k, v in
                      sorted(idle.items(), key=lambda kv: -kv[1])[:top]],
    }


def span_self_times(events) -> list[dict]:
    """Complete spans annotated with ``self_us``: duration minus the summed
    durations of direct children (same tid, contained in time)."""
    spans = [dict(e) for e in events if e.get("ph") == "X"]
    by_tid: dict = {}
    for s in spans:
        by_tid.setdefault(s.get("tid", 0), []).append(s)
    eps = 1e-3
    for ss in by_tid.values():
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

