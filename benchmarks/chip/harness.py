"""The benchmark's general machinery: finding a cell's files by name, the
chip check, counters of compiles and collections, the measured window and
the numbers taken from it.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own that this module finds by name:

- ``configs/<config>.json``: the configuration as it is run; its
  ``family`` names the module that runs it, ``families/<family>.py``, its
  ``reference`` the plain reference in ``refs/<reference>.py`` and its
  ``counts`` the operation counts in ``counts/<counts>.py``;
- ``traffic/<traffic>.json``: the parameters of the mix;
- ``limits/<workload>.json``: the limit of each number the cell compares
  with its reference;
- ``layers/<metric>.py``: the reader of a per-layer metric, named by the
  part of the metric's name before the first dot.
"""

from __future__ import annotations

import collections
import gc
import importlib.util
import json
import os
import sys
import threading
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))

# jax.monitoring events: a jit trace (a new shape met), a backend compile,
# and an executable read from the persistent compilation cache.
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoChip(SystemExit):
    """Raised where JAX finds no accelerator, or fewer chips than asked."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(workload: str, root: str = REPO, here: str = HERE):
    """``(workload entry, config, traffic, benchmark)`` of a cell by name."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; "
                         f"known: {sorted(cells)}")
    cell = cells[workload]
    config = load_json(os.path.join(here, "configs", cell["config"] + ".json"))
    traffic = load_json(os.path.join(here, "traffic",
                                     cell["traffic"] + ".json"))
    return cell, config, traffic, bench


def load_module(kind: str, name: str, here: str = HERE):
    """``<here>/<kind>/<name>.py`` as a module."""
    path = os.path.join(here, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    modname = f"_chipbench_{kind}_{name}".replace("-", "_").replace(".", "_")
    if modname in sys.modules:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def cell_limits(workload: str, here: str = HERE) -> dict:
    """The limits of a cell's compared numbers, ``{name: limit}``."""
    return load_json(os.path.join(here, "limits", workload + ".json"))


def decide(readings: dict, limits: dict) -> tuple[bool, dict]:
    """``(correct, checks)``: every limited reading at or under its limit,
    and something compared (a reading whose name starts with ``n_`` is a
    count of what was compared). ``checks`` is each number with its
    limit, as the result line prints them."""
    checks = {k: {"value": readings[k], "limit": lim}
              for k, lim in limits.items()}
    compared = any(v for k, v in readings.items() if k.startswith("n_"))
    correct = compared and all(c["value"] <= c["limit"]
                               for c in checks.values())
    return correct, checks


def cell_metrics(bench: dict, workload: str, trace: bool) -> list[dict]:
    """The metrics a run of this cell reports: its end-to-end metrics with
    ``trace`` off, its per-layer metrics with it on."""
    key = "per_layer" if trace else "end_to_end"
    return [m for m in bench[key]
            if "workloads" not in m or workload in m["workloads"]]


def require_chips(n: int) -> dict:
    """The device stamp; raises :class:`NoChip` off a TPU or short of
    ``n`` chips, before anything is printed to standard output."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"jax found no devices: {e}")
    d = devices[0]
    if d.platform != "tpu":
        raise NoChip(f"this benchmark runs on a TPU; jax found "
                     f"{d.platform!r} ({d.device_kind})")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips; jax found {len(devices)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": n}


def peaks_for(kind: str, here: str = HERE) -> dict:
    table = load_json(os.path.join(here, "peaks.json"))["devices"]
    if kind not in table:
        raise SystemExit(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


class CompileCounter:
    """Counts jit traces, backend compiles and persistent-cache loads."""

    def __init__(self):
        self.traces = self.compiles = self.cache_hits = 0
        self.names: list[str] = []     # what was traced or compiled, in order

    def on_duration(self, event: str, duration: float, **kw) -> None:
        if event == TRACE_EVENT:
            self.traces += 1
        elif event == COMPILE_EVENT:
            self.compiles += 1
        else:
            return
        self.names.append(f"{event.rsplit('/', 1)[-1]}:{kw.get('fun_name', '?')}")

    def on_event(self, event: str, **kw) -> None:
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def install(self) -> "CompileCounter":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)
        return self

    @property
    def total(self) -> int:
        return self.traces + self.compiles + self.cache_hits

    def snapshot(self) -> dict:
        return {"traces": self.traces, "compiles": self.compiles,
                "cache_loads": self.cache_hits}


class GCCounter:
    """Counts the interpreter's generation-2 collections (settings unchanged)."""

    def __init__(self):
        self.gen2 = 0
        gc.callbacks.append(self._cb)

    def _cb(self, phase: str, info: dict) -> None:
        if phase == "stop" and info.get("generation") == 2:
            self.gen2 += 1

    def close(self) -> None:
        if self._cb in gc.callbacks:
            gc.callbacks.remove(self._cb)


def percentile(xs, q: float) -> float:
    """Linear-interpolated percentile, numpy's default method (copied from
    ``repro.obs.metrics.percentile``). Empty input -> 0.0."""
    xs = sorted(float(x) for x in xs)
    if not xs:
        return 0.0
    if len(xs) == 1:
        return xs[0]
    pos = (q / 100.0) * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    frac = pos - lo
    return xs[lo] * (1.0 - frac) + xs[hi] * frac


def timing(xs) -> dict:
    """Median, 95th percentile and sample count of a list of seconds, in ms."""
    return {"p50_ms": percentile(xs, 50) * 1e3,
            "p95_ms": percentile(xs, 95) * 1e3, "n": len(xs)}


class StallSampler:
    """Where the host is in a round that runs long: while a round has run
    past ``after_s``, a daemon thread reads the stack of the thread that
    drives the rounds every ``every_s`` (it sleeps otherwise). A stall with
    no sample is one in which this thread could not run either: the
    interpreter's lock was held, or the process was not scheduled."""

    def __init__(self, after_s: float = 0.25, every_s: float = 0.05):
        self.after_s = after_s
        self.every_s = every_s
        self.tid = threading.get_ident()
        self.round = -1
        self.t_round = None          # start of the round in progress
        self.samples: dict[int, list[str]] = {}
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="stall-sampler")
        self._thread.start()

    def _loop(self) -> None:
        while not self._stop.wait(self.every_s):
            t0, rnd = self.t_round, self.round
            if t0 is None or time.perf_counter() - t0 < self.after_s:
                continue
            frame = sys._current_frames().get(self.tid)
            if frame is None:
                continue
            stack = traceback.extract_stack(frame)[-4:]
            self.samples.setdefault(rnd, []).append(" < ".join(
                f"{os.path.basename(f.filename)}:{f.lineno}:{f.name}"
                for f in reversed(stack)))

    def where(self, rnd: int) -> list:
        """The round's distinct samples with their counts, most first."""
        return collections.Counter(self.samples.get(rnd, [])).most_common(3)

    def close(self) -> None:
        self._stop.set()
        self._thread.join()


class Window:
    """The measured window: rounds driven through ``step()`` while the
    host clock is inside ``seconds``. Its edges fall on round boundaries:
    it opens just before a round starts and closes when the last round
    that started inside it returns."""

    def __init__(self, run, seconds: float, counter: CompileCounter,
                 gcc: GCCounter):
        self.run = run
        self.seconds = seconds
        self.counter = counter
        self.gcc = gcc
        self.rounds: list[tuple] = []
        self.t0 = self.t1 = 0.0
        self.stalls = None

    def drive(self) -> None:
        run, eng = self.run, self.run.eng
        counter, gcc = self.counter, self.gcc
        stalls = self.stalls = StallSampler()
        tiers = eng.stats.tier_rounds
        rounds = self.rounds
        self.c0 = counter.snapshot()
        self.n0 = len(counter.names)
        self.g0 = gcc.gen2
        self.t0 = t = time.perf_counter()
        run.open_window(self.t0)
        end = self.t0 + self.seconds
        while t < end:
            c, g, before = counter.total, gcc.gen2, dict(tiers)
            stalls.round, stalls.t_round = len(rounds), t
            eng.step()
            t1 = time.perf_counter()
            stalls.t_round = None
            n = run.after_step(t1)
            tier = ",".join(k for k, v in tiers.items()
                            if v != before.get(k, 0))
            rounds.append((t, t1, n, tier, counter.total - c, gcc.gen2 - g))
            t = t1
        self.t1 = t
        run.close_window(self.t1)
        stalls.close()

    @property
    def length_s(self) -> float:
        return self.t1 - self.t0

    def diagnostics(self) -> dict:
        by_tier: dict[str, int] = {}
        for r in self.rounds:
            by_tier[r[3] or "none"] = by_tier.get(r[3] or "none", 0) + 1
        c1 = self.counter.snapshot()
        slow = sorted(range(len(self.rounds)),
                      key=lambda i: self.rounds[i][0] - self.rounds[i][1])
        durs = [r[1] - r[0] for r in self.rounds]
        return {
            "rounds": len(self.rounds),
            "round_ms": timing(durs),
            "compiles_in_window": {k: c1[k] - self.c0[k] for k in c1},
            "compiled_in_window": self.counter.names[self.n0:][:20],
            "rounds_by_tier": by_tier,
            "gen2_collections_in_window": self.gcc.gen2 - self.g0,
            "slowest_rounds": [
                {"round": i, "ms": durs[i] * 1e3, "units": self.rounds[i][2],
                 "tier": self.rounds[i][3], "compile_events":
                 self.rounds[i][4], "gen2": self.rounds[i][5],
                 "where": self.stalls.where(i)}
                for i in slow[:10]],
        }


def memory_peak_bytes() -> int | None:
    import jax

    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def emit(result: dict, checks: dict) -> None:
    """The compared numbers as the last lines of standard error, and the
    result as the last line of standard output, with ``checks`` last."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    result = dict(result)
    result["checks"] = checks
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
