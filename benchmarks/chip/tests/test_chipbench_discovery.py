"""A new configuration, traffic mix and per-layer metric are found by name
from files of their own, with no existing file edited."""

import chipbench_paths  # noqa: F401  (first: the path to the benchmark)

import hashlib
import json
import os
import shutil
import subprocess
import sys

import harness

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(BENCH))


def _digest(root: str) -> dict:
    out = {}
    for d, _, files in os.walk(root):
        if "__pycache__" in d:
            continue
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[p] = hashlib.sha256(fh.read()).hexdigest()
    return out


# Run inside a fresh interpreter from the copy: the copy's own harness
# finds the cell through the copy's BENCHMARK.json; the chip check is
# skipped, as in the fault tests.
DRIVE = """
import json, sys
sys.path.insert(0, sys.argv[1])
import harness, run_cell
cell, cfg, traffic, bench = harness.find_cell(sys.argv[2])
result, checks, _ = run_cell.execute(
    sys.argv[2], 3000000013, 1.0, True, cfg, traffic, bench,
    {"platform": "cpu", "kind": "cpu", "count": 1},
    {"flops_per_s": 1e12, "bytes_per_s": 1e11},
    harness.cell_limits(sys.argv[2]))
print(json.dumps(dict(result, checks=checks)))
"""


def test_new_cell_runs_from_new_files_alone(tmp_path):
    """A configuration, a traffic mix, the cell's limits and a per-layer
    metric, each a new file, plus entries in ``BENCHMARK.json``: a copy of
    the benchmark finds them all by name and runs the cell through
    ``run_cell.execute`` at a tiny size on the CPU. No file of the
    benchmark is edited."""
    before = _digest(BENCH)
    here = tmp_path / "benchmarks" / "chip"
    shutil.copytree(BENCH, here, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    bench = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    bench["workloads"].append(
        {"name": "tiny-lm.short", "config": "tiny-lm", "traffic": "short",
         "chips": 1, "why": "a cell added by files alone"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("tiny-lm.short")
    bench["per_layer"].append(
        {"name": "tiny_metric.tokens", "unit": "ms", "better": "lower",
         "source": "program_span", "layer": "round loop",
         "moves": "tokens_per_s", "workloads": ["tiny-lm.short"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cfg = harness.load_json(os.path.join(BENCH, "configs",
                                         "lstmlm-ptb-medium.json"))
    cfg.update(name="tiny-lm", embed=16, hidden=16, vocab=32, max_slots=4)
    (here / "configs" / "tiny-lm.json").write_text(json.dumps(cfg))
    (here / "traffic" / "short.json").write_text(json.dumps(
        {"family": "lm", "loop": "closed", "clients": 4,
         "prompt_len": [3, 5], "max_new": [4, 6], "setup_turnovers": 1,
         "check_finished": 2, "stagger": {"prompt_len": 4, "span_rounds": 6,
                                          "group_sizes": [2]}}))
    (here / "limits" / "tiny-lm.short.json").write_text(json.dumps(
        {"logit_gap": 1e-2, "step_rms": 1e-6}))
    (here / "layers" / "tiny_metric.py").write_text(
        "def read(ctx, variant):\n    return float(len(ctx['run'].ttft))\n")

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([os.path.join(REPO, "src"),
                                           os.environ.get("PYTHONPATH", "")]))
    p = subprocess.run([sys.executable, "-c", DRIVE, str(here),
                        "tiny-lm.short"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"], result["checks"]
    assert result["metrics"]["tiny_metric.tokens"]["value"] > 0
    assert "host_ms_per_round.itl" in result["metrics"]
    assert set(result["checks"]) == {"logit_gap", "step_rms"}
    assert _digest(BENCH) == before


def test_every_listed_metric_has_a_reader_and_every_cell_its_files():
    bench = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    for w in bench["workloads"]:
        _, cfg, _, _ = harness.find_cell(w["name"])
        for kind, key in (("families", "family"), ("refs", "reference"),
                          ("counts", "counts")):
            assert os.path.isfile(os.path.join(BENCH, kind,
                                               cfg[key] + ".py"))
        assert harness.cell_limits(w["name"])
    for m in bench["per_layer"]:
        reader = harness.load_module("layers", m["name"].partition(".")[0])
        assert callable(reader.read)


def test_no_chip_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run_cell.py"), "--workload",
         "lstmlm-ptb-medium.decode-sat", "--seed", "3000000001", "--seconds", "1",
         "--trace", "0"], cwd=REPO, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_benchmark_file_keeps_its_contract():
    import re

    bench = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    cells = {w["name"]: w for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and name.match(m["name"])

    def reports(cell, metric):
        return "workloads" not in metric or cell in metric["workloads"]

    for m in bench["per_layer"]:
        assert name.match(m["name"]) and m["moves"] in e2e
        for c in m["workloads"]:
            assert reports(c, e2e[m["moves"]]), (m["name"], c)
    for c, w in cells.items():
        assert name.match(c) and len(w["why"]) <= 200 and w["chips"] in (1, 4)
        assert [m for m in bench["end_to_end"]
                if reports(c, m) and m["name"] != "setup_s"]
        assert [m for m in bench["per_layer"] if reports(c, m)]
    for cfg in bench["configs"]:
        assert len(cfg["why"]) <= 200 and cfg["file"].startswith(
            bench["paths"][0] + "/")
