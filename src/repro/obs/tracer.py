"""Zero-dependency span/event tracer for the serve path (DESIGN.md §6).

One :class:`Tracer` instance records a flat stream of *complete spans*
(named intervals with a start and a duration) and *instant events* (named
points), grouped into per-round buckets so the flight recorder can keep a
ring of the last N rounds without retaining a whole serving session.

Design constraints, in order:

- **Disabled must cost nothing measurable.** ``span()`` on a disabled
  tracer returns a shared no-op context manager and ``event()`` returns
  immediately — no allocation, no lock, no timestamp. The serve engine and
  the plan executors call these hooks unconditionally; the obs-smoke CI job
  gates the enabled-vs-disabled overhead at < 5% wall on the churn trace.
- **Thread-safe.** Span nesting state is thread-local (each thread has its
  own open-span stack); the event buffer is guarded by one re-entrant
  lock. The sharded engine packs host-side index vectors while a dispatch
  is in flight, and tests hammer the tracer from many threads. The lock is
  re-entrant because a garbage collection can start on a thread that
  holds it, and the GC hook below records through the same lock.
- **The runtime beside the program.** While enabled, a ``gc.callbacks``
  hook records every generation-1 and -2 collection as a ``gc.collect``
  span and sums the time of every collection (:attr:`Tracer.gc_s`), and a
  ``jax.monitoring`` listener records jaxpr traces and backend compiles as
  ``jax.trace`` / ``jax.compile`` spans. Both are installed when
  ``enabled`` turns on and removed when it turns off; a disabled tracer
  leaves ``gc.callbacks`` and JAX's listeners untouched. A ring tracer
  (the flight recorder's always-on mode) installs neither and stamps no
  round counters: it records the program's spans and nothing more.
- **One clock with the device.** ``Tracer(annotate=True)`` opens a
  ``jax.profiler.TraceAnnotation`` of the same name beside every span, so
  a ``jax.profiler`` trace holds the program's spans and the device's
  operations on one clock. JAX is imported only when the bridge is on.
- **Perfetto-viewable output.** :meth:`to_chrome` emits the Chrome
  trace-event JSON format (``{"traceEvents": [...]}`` with ``ph: "X"``
  complete spans and ``ph: "i"`` instants, timestamps in microseconds), so
  a recorded serve trace opens directly in Perfetto / chrome://tracing.

Span taxonomy (what the serve stack records — see DESIGN.md §6 for the
full vocabulary):

- ``serve.run`` / ``serve.round`` — engine loop and one scheduler round
  (``serve.round`` carries the loop thread's ``cpu_ms``, ``gc_ms`` and,
  where the host counts them, ``nivcsw`` and ``majflt`` over the round,
  see :meth:`Tracer.counted_span`),
  ``serve.poll_compiles`` — the compile-service heartbeat before a round,
- ``round.schedule`` / ``round.pack`` / ``round.lm`` / ``round.single`` /
  ``round.scatter`` / ``round.feed`` / ``round.feed_stage`` — engine-side
  round phases (planning, feed-graph packing, family sub-rounds, state
  scatter-back, token feed, prefill slot staging); inside ``round.lm``,
  ``round.dispatch`` (the engine's side of launching the round) holding
  ``round.lookup`` (the dispatch-side cache and quarantine probes),
  ``round.speculate`` (the next round's plan and pack, ahead of this
  round's commit) holding ``round.spec_check`` / ``round.spec_snapshot``
  (whether and from what to speculate), ``round.settle`` (the block, and
  the quarantine book-keeping once the device work is in),
  ``round.release`` (freeing the round's graph and results); inside
  ``round.scatter``,
  ``round.commit`` (the state scatter-back and argmax dispatch) and
  ``round.readback`` (every host read of a device array); pipelined rounds
  (DESIGN.md §9) stamp speculative ``round.schedule``/``round.pack`` spans
  with ``overlap`` and the commit-side residue with ``promoted``,
- ``plan.pack`` / ``plan.schedule`` / ``plan.lower`` / ``plan.h2d`` /
  ``plan.dispatch`` / ``plan.block`` — executor-side phases (host packing,
  host-to-device transfer, dispatch, block-until-ready device execution),
- ``xla.compile`` — one span per XLA executable build, attributed to its
  bucket signature (``bucket=<digest>``) and lowering seconds,
- ``jax.trace`` / ``jax.compile`` — every jaxpr trace and backend compile
  JAX reports, with ``fun_name`` and the engine ``round`` it fell in,
- ``gc.collect`` — a generation-1 or -2 collection (``gen``,
  ``collected``),
- ``interp.schedule`` / ``interp.exec`` — the interpreted floor,
- ``req.*`` instants — request lifecycle (queued, admitted, prefill, ttft,
  completed, failed, timed_out, rejected) plus ``quarantine`` bookings.
"""

from __future__ import annotations

import gc
import json
import threading
import time
import weakref
from collections import deque
from typing import Any

try:
    import resource
    _RUSAGE_THREAD = resource.RUSAGE_THREAD
    # A host that counts no page faults for a running interpreter (a
    # sandboxed kernel) counts no switches either: stamp nothing there
    # rather than zeros.
    if resource.getrusage(resource.RUSAGE_SELF).ru_minflt == 0:
        resource = None
except (ImportError, AttributeError):    # not Linux: no per-thread rusage
    resource = None

# jax.monitoring time spans the tracer records, by the span name it gives.
_JAX_SPANS = {"/jax/core/compile/jaxpr_trace_duration": "jax.trace",
              "/jax/core/compile/backend_compile_duration": "jax.compile"}


class _NullSpan:
    """Shared no-op span: what a disabled tracer hands out."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args) -> None:
        pass


NULL_SPAN = _NullSpan()


class _Span:
    """One live span; records itself into the tracer on exit. ``ann`` is
    the profiler annotation opened beside it, or ``None``."""

    __slots__ = ("_tr", "name", "cat", "args", "_t0", "_ann", "_stack")

    def __init__(self, tracer: "Tracer", name: str, cat: str, args: dict,
                 ann=None):
        self._tr = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self._ann = ann

    def set(self, **args) -> None:
        """Attach/overwrite args mid-span (e.g. a compile duration that is
        only known at the end of the guarded region)."""
        self.args.update(args)

    # The span's own book-keeping runs between its two clock reads, so
    # what a span costs lands in its own time and not in its parent's
    # self time.
    def __enter__(self):
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        self._stack = self._tr._stack()
        self._stack.append(None)
        return self

    def __exit__(self, *exc):
        tr = self._tr
        if self._stack:
            self._stack.pop()
        ev = {"name": self.name, "cat": self.cat, "ph": "X", "ts": 0.0,
              "dur": 0.0, "pid": 0, "tid": tr._tid(), "args": self.args}
        t0, t1 = self._t0, time.perf_counter()
        ev["ts"] = (t0 - tr._epoch) * 1e6
        ev["dur"] = (t1 - t0) * 1e6
        tr._record(ev)
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False


class _Counted:
    """Wraps an enabled span: on exit, stamps onto it what the calling
    thread spent inside it (see :meth:`Tracer.counted_span`)."""

    __slots__ = ("_tr", "_sp", "_inner", "_c0")

    def __init__(self, tracer: "Tracer", span):
        self._tr = tracer
        self._sp = span

    def __enter__(self):
        self._inner = self._sp.__enter__()
        self._c0 = self._tr._thread_counters()
        return self._inner

    def __exit__(self, *exc):
        c0, c1 = self._c0, self._tr._thread_counters()
        args = {"cpu_ms": (c1[0] - c0[0]) * 1e3,
                "gc_ms": (c1[1] - c0[1]) * 1e3}
        if len(c1) > 2:
            args.update(nivcsw=c1[2] - c0[2], majflt=c1[3] - c0[3])
        self._inner.set(**args)
        return self._sp.__exit__(*exc)


class Tracer:
    """Span/event recorder with per-round buckets.

    ``enabled`` may be flipped at any time (the benchmark helpers enable
    the process-default tracer after parsing ``--trace-out``); turning it
    on installs the GC hook and the ``jax.monitoring`` listener, turning
    it off removes them. ``ring > 0`` keeps only the last ``ring`` round
    buckets — the flight-recorder mode, bounding memory for always-on
    fault capture, which installs no hooks and stamps no counters;
    ``ring=0`` keeps the whole session for ``--trace-out`` export.
    ``annotate=True`` opens a ``jax.profiler.TraceAnnotation`` beside
    every span.
    """

    def __init__(self, enabled: bool = False, ring: int = 0,
                 annotate: bool = False):
        self.ring = int(ring)
        self.annotate = bool(annotate)
        self._lock = threading.RLock()
        self._local = threading.local()
        # Buckets of (round_id | None, [event dict, ...]); the first bucket
        # (round None) holds anything recorded before the first round.
        self._buckets: deque = deque([[None, []]])
        self._tids: dict[int, int] = {}
        self._epoch = time.perf_counter()
        self._stacks: list[list] = []   # every thread's open-span stack
        self.n_dropped = 0      # events discarded by ring rotation
        self.gc_s = 0.0         # seconds in collections while enabled
        self._gc_t0 = None      # start of the collection in progress
        self._gc_span = None    # (span, entered span) of that collection
        self._annotation = None  # jax.profiler.TraceAnnotation, once needed
        self._hooks = None      # finalizer that removes the runtime hooks
        self._enabled = False
        self.enabled = enabled

    @property
    def enabled(self) -> bool:
        return self._enabled

    @enabled.setter
    def enabled(self, on: bool) -> None:
        on = bool(on)
        if on == self._enabled:
            return
        self._enabled = on
        if on and not self.ring:
            self._hooks = _install_runtime_hooks(self)
        elif self._hooks is not None:
            hooks, self._hooks = self._hooks, None
            hooks()

    # -- recording ----------------------------------------------------------

    def span(self, name: str, cat: str = "serve", **args):
        """Context manager timing a named region. No-op when disabled."""
        if not self._enabled:
            return NULL_SPAN
        ann = self._annotate(name) if self.annotate else None
        return _Span(self, name, cat, args, ann)

    def counted_span(self, name: str, cat: str = "serve", **args):
        """:meth:`span` that also stamps, on exit, what the calling thread
        spent inside it: ``cpu_ms`` (its on-CPU time, ``time.thread_time``),
        ``gc_ms`` (time in collections of any generation, on any thread)
        and, on a Linux host that counts them, ``nivcsw`` (involuntary
        context switches) and ``majflt`` (major page faults) from
        ``getrusage(RUSAGE_THREAD)``. Wall minus ``cpu_ms`` is the time the
        thread was off the CPU: descheduled (``nivcsw``), or waiting on the
        device, a lock or the interpreter's lock. ``cpu_ms`` is as fine as
        the host's thread clock: a sandboxed kernel may tick it in 10 ms,
        so that only a mean over many spans stands. A ring tracer stamps
        nothing (:class:`Tracer`)."""
        sp = self.span(name, cat, **args)
        return sp if sp is NULL_SPAN or self.ring else _Counted(self, sp)

    def complete(self, name: str, t0: float, t1: float, cat: str = "serve",
                 **args) -> None:
        """Record a span known only after the fact, from ``t0`` to ``t1``
        on the ``time.perf_counter`` clock. No-op when disabled."""
        if not self._enabled:
            return
        self._record({"name": name, "cat": cat, "ph": "X",
                      "ts": (t0 - self._epoch) * 1e6,
                      "dur": max(t1 - t0, 0.0) * 1e6,
                      "pid": 0, "tid": self._tid(), "args": args})

    def event(self, name: str, cat: str = "serve", **args) -> None:
        """Record an instant event. No-op when disabled."""
        if not self._enabled:
            return
        self._record({"name": name, "cat": cat, "ph": "i", "s": "t",
                      "ts": (time.perf_counter() - self._epoch) * 1e6,
                      "pid": 0, "tid": self._tid(), "args": args})

    def _record(self, ev: dict) -> None:
        with self._lock:
            self._buckets[-1][1].append(ev)

    def mark_round(self, round_id: int) -> None:
        """Open a new per-round bucket (subsequent events land in it). With
        ``ring > 0``, buckets beyond the ring are dropped oldest-first."""
        if not self._enabled:
            return
        with self._lock:
            self._buckets.append([int(round_id), []])
            while self.ring and len(self._buckets) > self.ring:
                self.n_dropped += len(self._buckets[0][1])
                self._buckets.popleft()

    def _tid(self) -> int:
        """Small stable per-thread id (Chrome tids render better small)."""
        try:
            return self._local.tid
        except AttributeError:
            ident = threading.get_ident()
            with self._lock:
                tid = self._tids.setdefault(ident, len(self._tids))
            self._local.tid = tid
            return tid

    def _stack(self) -> list:
        """This thread's open-span stack; every thread's is also listed in
        ``_stacks``, so entering a span takes no lock."""
        try:
            return self._local.stack
        except AttributeError:
            st = self._local.stack = []
            with self._lock:
                self._stacks.append(st)
            return st

    # -- the runtime beside the program ---------------------------------------

    def _annotate(self, name: str):
        cls = self._annotation
        if cls is None:
            from jax.profiler import TraceAnnotation as cls
            self._annotation = cls
        return cls(name)

    def _thread_counters(self) -> tuple:
        if resource is None:
            return (time.thread_time(), self.gc_s)
        ru = resource.getrusage(_RUSAGE_THREAD)
        return (time.thread_time(), self.gc_s, ru.ru_nivcsw, ru.ru_majflt)

    def _on_gc(self, phase: str, info: dict) -> None:
        """``gc.callbacks`` hook. Entered by hand through :meth:`span`, so
        a subclass's span (one that annotates the profiler's trace) covers
        collections too. Collections never nest, so one slot holds the
        open one; a start or stop whose partner was missed is dropped."""
        if phase == "start":
            self._end_gc(None)
            self._gc_t0 = time.perf_counter()
            if info["generation"] >= 1:
                sp = self.span("gc.collect", cat="gc",
                               gen=info["generation"])
                self._gc_span = (sp, sp.__enter__())
        else:
            self._end_gc(info)

    def _end_gc(self, info: dict | None) -> None:
        t0, self._gc_t0 = self._gc_t0, None
        if t0 is not None and info is not None:
            self.gc_s += time.perf_counter() - t0
        open_span, self._gc_span = self._gc_span, None
        if open_span is not None:
            sp, inner = open_span
            if info is not None:
                inner.set(collected=info.get("collected", 0))
            sp.__exit__(None, None, None)

    def _on_jax_span(self, name: str, start: float, end: float,
                     fun_name: str) -> None:
        """A ``jax.monitoring`` time span (wall clock) as a complete span on
        this tracer's clock, stamped with the round it fell in."""
        off = time.perf_counter() - time.time()
        self.complete(name, start + off, end + off, cat="compile",
                      fun_name=fun_name, round=self._buckets[-1][0])

    # -- introspection ------------------------------------------------------

    @property
    def events(self) -> list[dict]:
        """Flat copy of every retained event, in record order."""
        with self._lock:
            return [ev for _, evs in self._buckets for ev in evs]

    def spans(self, name: str | None = None) -> list[dict]:
        """Retained complete spans, optionally filtered by name."""
        return [e for e in self.events
                if e["ph"] == "X" and (name is None or e["name"] == name)]

    def open_spans(self) -> int:
        """Spans entered but not exited — 0 after any balanced run."""
        with self._lock:
            return sum(len(st) for st in self._stacks)

    def depth(self) -> int:
        """Current thread's span nesting depth."""
        return len(self._stack())

    def recent_rounds(self, n: int) -> list[dict]:
        """The last ``n`` round buckets as ``{"round", "events"}`` dicts —
        what the flight recorder snapshots into a dump."""
        with self._lock:
            tail = list(self._buckets)[-n:]
            return [{"round": rid, "events": list(evs)} for rid, evs in tail]

    def clear(self) -> None:
        with self._lock:
            self._buckets = deque([[None, []]])
            self.n_dropped = 0

    # -- export -------------------------------------------------------------

    def to_chrome(self, process_name: str = "repro-serve") -> dict:
        """The Chrome trace-event JSON object (Perfetto-viewable)."""
        meta = [{"name": "process_name", "ph": "M", "pid": 0, "tid": 0,
                 "args": {"name": process_name}}]
        with self._lock:
            meta += [{"name": "thread_name", "ph": "M", "pid": 0, "tid": tid,
                      "args": {"name": f"thread-{tid}"}}
                     for tid in sorted(self._tids.values())]
            evs = [dict(ev, args=_json_safe(ev["args"]))
                   for _, evs in self._buckets for ev in evs]
        return {"traceEvents": meta + evs, "displayTimeUnit": "ms"}

    def write(self, path: str, process_name: str = "repro-serve") -> None:
        with open(path, "w") as f:
            json.dump(self.to_chrome(process_name), f)


def _install_runtime_hooks(tracer: Tracer) -> weakref.finalize:
    """Hook ``tracer`` into ``gc.callbacks`` and ``jax.monitoring``. The
    hooks hold the tracer weakly; the returned finalizer removes them, and
    runs on its own if the tracer is dropped while still enabled."""
    ref = weakref.ref(tracer)

    def on_gc(phase, info):
        tr = ref()
        if tr is not None:
            tr._on_gc(phase, info)

    def on_time_span(event, start, end, **kw):
        name = _JAX_SPANS.get(event)
        tr = ref()
        if name is not None and tr is not None:
            tr._on_jax_span(name, start, end, kw.get("fun_name", "?"))

    gc.callbacks.append(on_gc)
    try:
        import jax.monitoring as monitoring
    except ImportError:
        monitoring = None
    else:
        monitoring.register_event_time_span_listener(on_time_span)
    return weakref.finalize(tracer, _remove_runtime_hooks, on_gc,
                            on_time_span, monitoring)


def _remove_runtime_hooks(on_gc, on_time_span, monitoring) -> None:
    if on_gc in gc.callbacks:
        gc.callbacks.remove(on_gc)
    if monitoring is not None:
        try:
            monitoring.unregister_event_time_span_listener(on_time_span)
        except (AssertionError, ValueError):
            pass    # someone cleared JAX's listeners already


def _json_safe(obj: Any):
    """Args must serialize: stringify anything JSON cannot carry."""
    if isinstance(obj, dict):
        return {str(k): _json_safe(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_json_safe(v) for v in obj]
    if isinstance(obj, (str, int, float, bool)) or obj is None:
        return obj
    return repr(obj)


def validate_chrome_trace(obj: Any) -> list[str]:
    """Schema check for an exported trace: returns a list of problems
    (empty = valid). Shared by tests and the obs-smoke gate."""
    problems: list[str] = []
    if not isinstance(obj, dict) or "traceEvents" not in obj:
        return ["top level must be an object with a 'traceEvents' list"]
    evs = obj["traceEvents"]
    if not isinstance(evs, list):
        return ["'traceEvents' is not a list"]
    for i, ev in enumerate(evs):
        if not isinstance(ev, dict):
            problems.append(f"event {i} is not an object")
            continue
        ph = ev.get("ph")
        if ph not in ("X", "i", "M"):
            problems.append(f"event {i} has unknown phase {ph!r}")
            continue
        if "name" not in ev or "pid" not in ev or "tid" not in ev:
            problems.append(f"event {i} missing name/pid/tid")
        if ph in ("X", "i") and not isinstance(ev.get("ts"), (int, float)):
            problems.append(f"event {i} ({ev.get('name')}) has no numeric ts")
        if ph == "X":
            dur = ev.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                problems.append(
                    f"event {i} ({ev.get('name')}) has bad dur {dur!r}")
        args = ev.get("args", {})
        try:
            json.dumps(args)
        except TypeError:
            problems.append(f"event {i} args not JSON-serializable")
    return problems


# The process-default tracer: disabled until something (the benchmark
# helpers' --trace-out, a test) enables it. Engines and executors fall back
# to it when not handed an explicit tracer, so a single flag lights up the
# whole stack without threading a tracer through every constructor.
_DEFAULT = Tracer(enabled=False)

# Dedicated always-disabled instance for call sites that must never record
# (do not enable this one).
NULL_TRACER = Tracer(enabled=False)


def default_tracer() -> Tracer:
    return _DEFAULT
