"""Run one benchmark cell on the chip and print its result line.

    python3 benchmarks/chip/run_cell.py \
        --workload lstmlm-ptb-medium.decode-sat --seed 7 --seconds 30 --trace 0

The cell, its configuration and its traffic are found by name through
``BENCHMARK.json`` (see ``harness.py``). The run builds the configuration's
model (weights from its ``weight_seed``), trains its FSM batching policy
into a registry under ``.bench_out/<workload>/``, warms the cell's own
shapes, then drives ``ServeEngine.step()`` for ``--seconds`` and stamps
every submission and delivery on the host clock. With ``--trace 1`` the same window runs under
the profiler and the program's spans, and the per-layer metrics are read
from them. After the window the served outputs are compared with the plain
reference in ``refs/`` and judged against the cell's own limits in
``limits/<workload>.json``.

Standard output ends with one JSON line: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` with ``--trace 1``),
then ``checks``, each compared number with its limit. Earlier lines carry
the set-up breakdown, the per-round diagnostics and each timing's median
and sample count. Without a TPU, or with fewer chips than the cell needs,
the run exits 3 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(REPO, "src"))

import harness  # noqa: E402
import tracing  # noqa: E402


def pin_compile_cache() -> None:
    """Keep JAX's persistent compilation cache at a fixed path inside the
    checkout, whatever the environment names, and uncapped: a bucket
    program holds the weights as constants (about 180 MB at the PTB-medium
    widths), and a capped cache refuses it, so every run would compile
    again. Call before anything imports JAX, which reads both once."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(REPO, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"


def build(cfg: dict, seed: int, out_dir: str, tracer, phases: dict):
    """The configuration's model (built by its family module at the
    configuration's widths), its trained policy, and the engine with the
    serve launcher's defaults."""
    t = time.perf_counter()
    from repro.launch.serve import train_policies
    from repro.obs import Obs
    from repro.serve import PolicyRegistry, ServeEngine

    fam = cfg["family"]
    workloads = {fam: harness.load_module("families", fam).make_workload(
        cfg, seed)}
    phases["imports_and_model_init_s"] = time.perf_counter() - t
    t = time.perf_counter()
    reg_dir = os.path.join(out_dir, "registry")
    shutil.rmtree(reg_dir, ignore_errors=True)
    registry = PolicyRegistry(reg_dir)
    # The policy is part of the deployment, not of the inputs: one fixed
    # training seed, so every run batches the same way.
    train_policies(registry, [fam], workloads, 0)
    phases["policy_training_s"] = time.perf_counter() - t
    eng = ServeEngine(workloads, compiled=True, bucketed=True,
                      continuous=True, max_slots=cfg["max_slots"],
                      model_size=cfg["hidden"], seed=seed,
                      registry=registry, async_compile=True,
                      compile_workers=2, pipeline=True,
                      obs=Obs(tracer=tracer))
    return workloads, eng


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    pin_compile_cache()
    cell, cfg, traffic, bench = harness.find_cell(args.workload)
    try:
        device = harness.require_chips(cell["chips"])
    except harness.NoChip as e:
        print(f"run_cell: {e}", file=sys.stderr)
        return 3
    peaks = harness.peaks_for(device["kind"])
    limits = harness.cell_limits(args.workload)
    result, checks, _ = execute(args.workload, args.seed, args.seconds,
                                bool(args.trace), cfg, traffic, bench, device,
                                peaks, limits)
    harness.emit(result, checks)
    return 0


def execute(workload: str, seed: int, seconds: float, trace: bool,
            cfg: dict, traffic: dict, bench: dict, device: dict,
            peaks: dict, limits: dict) -> tuple[dict, dict, object]:
    """Everything after the chip check: set-up, the window, the numbers
    and the comparison. Returns the result line, the checks and the run
    (whose ``readings`` can be taken again, as the control does)."""
    seed = seed % (1 << 63)
    from repro.launch.jaxcache import enable_compilation_cache
    enable_compilation_cache()
    counter = harness.CompileCounter().install()
    gcc = harness.GCCounter()

    out_dir = os.path.join(harness.REPO, ".bench_out", workload)
    os.makedirs(out_dir, exist_ok=True)
    tracer = tracing.AnnotatingTracer(enabled=False)
    phases: dict = {"start_and_device_init_s": time.perf_counter() - T_START}
    workloads, eng = build(cfg, seed, out_dir, tracer, phases)
    fam = harness.load_module("families", cfg["family"])
    run = fam.Run(cfg, traffic, seed, workloads, eng, counter)
    c_setup = counter.snapshot()
    run.setup(phases)
    phases["compiles_in_setup"] = {k: v - c_setup[k] for k, v in
                                   counter.snapshot().items()}

    window = harness.Window(run, seconds, counter, gcc)
    trace_dir = os.path.join(out_dir, f"trace-{os.getpid()}")
    if trace:
        import jax

        tracer.clear()
        tracer.enabled = True
        jax.profiler.start_trace(trace_dir,
                                 profiler_options=tracing.profile_options())
        with jax.profiler.TraceAnnotation(tracing.WINDOW):
            window.drive()
        tracer.enabled = False
        jax.profiler.stop_trace()
    else:
        window.drive()
    setup_s = window.t0 - T_START
    mem = harness.memory_peak_bytes()
    run.after_window()

    print("setup " + json.dumps(dict(phases, setup_s=setup_s)))
    print("timings " + json.dumps(run.timings()))
    print("diagnostics " + json.dumps(window.diagnostics()))

    metrics: dict = {}
    result_device = dict(device, memory_peak_bytes=mem)
    breakdown = None
    if trace:
        events = tracing.read_xplane(trace_dir)
        tr = tracing.reduce_trace(events)
        shutil.rmtree(trace_dir, ignore_errors=True)
        spans = tracing.span_self_times(tracer.spans())
        ctx = {"run": run, "cfg": cfg, "traffic": traffic, "peaks": peaks,
               "trace": tr, "spans": spans, "window_s": window.length_s,
               "model_flops": run.model_flops()}
        for m in harness.cell_metrics(bench, workload, trace=True):
            family = m["name"].partition(".")[0]
            v = harness.load_module("layers", family).read(ctx, m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if tr is not None:
            result_device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
            breakdown = {"device_ops": tr["device_ops"],
                         "idle_gaps": tr["idle_gaps"]}
    else:
        e2e = dict(run.end_to_end(), setup_s=setup_s)
        for m in harness.cell_metrics(bench, workload, trace=False):
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    readings = run.readings()
    eng.close()
    gcc.close()
    print("readings " + json.dumps(readings))
    correct, checks = harness.decide(readings, limits)
    result = {"correct": correct, "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics,
              "device": result_device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    return result, checks, run


if __name__ == "__main__":
    raise SystemExit(main())
