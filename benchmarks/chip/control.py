"""Readings of a cell's compared numbers, for setting their limits.

For each seed, one short run of the cell through the timed path
(``run_cell.execute``), then the compared numbers three times over the
same sample, each judged by the cell's limits with the same rule as the
run's ``correct`` (``harness.decide``):

- ``program``: the program against the reference at the configuration's
  precision;
- ``control``: the reference at the nearest lower precision (``high``,
  three bf16 passes, for float32 at ``highest``) in the program's place;
  its verdict has to be false;
- ``token_altered``: the program's served tokens each moved by one id, the
  fault of a token altered where it is produced; its verdict has to be
  false.

The benchmark's own runs do not run this.

    python3 benchmarks/chip/control.py \\
        --workload lstmlm-ptb-medium.decode-sat \\
        --seeds 101,102,103 --seconds 8 --out .bench_out/control.jsonl
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run_cell  # noqa: E402  (puts src on the path)

import harness  # noqa: E402

# The configuration states float32 at HIGHEST; the nearest precision below
# is three bf16 passes.
CONTROL = "high"


def judged(run, limits: dict) -> dict:
    """The three readings of one run, each with its verdict."""
    out = {}
    for kind, kw in (("program", {}), ("control", {"control": CONTROL}),
                     ("token_altered", {"shift": 1})):
        r = run.readings(**kw)
        r["correct"] = harness.decide(r, limits)[0]
        out[kind] = r
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one run each")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    run_cell.pin_compile_cache()
    cell, cfg, traffic, bench = harness.find_cell(args.workload)
    try:
        device = harness.require_chips(cell["chips"])
    except harness.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 3
    peaks = harness.peaks_for(device["kind"])
    limits = harness.cell_limits(args.workload)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "a") as f:
        for seed in (int(s) for s in args.seeds.split(",")):
            result, checks, run = run_cell.execute(
                args.workload, seed, args.seconds, False, cfg, traffic,
                bench, device, peaks, limits)
            row = dict({"workload": args.workload, "seed": seed,
                        "correct": result["correct"],
                        "metrics": {k: v["value"]
                                    for k, v in result["metrics"].items()}},
                       **judged(run, limits))
            print("control-row " + json.dumps(row), flush=True)
            f.write(json.dumps(row) + "\n")
            f.flush()
            del run
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
