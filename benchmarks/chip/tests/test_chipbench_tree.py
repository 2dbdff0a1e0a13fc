"""The tree cell (``treelstm-sst-const.classify-sat``) at a tiny size on
the CPU, the chip check skipped: an unbroken run reaches a result line and
is correct, and each fault the served tree path can have makes the run not
correct (the comparison that decides ``correct``, ``families/tree.py``)."""

import chipbench_paths  # noqa: F401  (first: the path to the benchmark)

import json
import os
import subprocess
import sys

import pytest

import harness
import run_cell

CELL = "treelstm-sst-const.classify-sat"
LIMITS = harness.cell_limits(CELL)
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
PEAKS = {"flops_per_s": 1e12, "bytes_per_s": 1e11}
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(BENCH))
TINY = {"embed": 12, "hidden": 8, "vocab": 50}
# 16 sentences a wave, their lengths spread over the cell's table.
SHORT = {"clients": 16, "lengths": [3, 4, 6, 7, 9, 10, 11, 12, 13, 14, 15,
                                    17, 19, 21, 24, 28],
         "prewarm_waves": 2, "prewarm_depth": 16, "setup_turnovers": 1,
         "check_finished": 24}


def run(widths: dict | None = TINY, seconds: float = 1.0):
    """A whole run of the cell on the CPU with its traffic shortened; at a
    tiny width, or at the configuration's own with ``widths=None``."""
    _, cfg, traffic, bench = harness.find_cell(CELL)
    return run_cell.execute(CELL, 3000000037, seconds, False,
                            dict(cfg, **(widths or {})),
                            dict(traffic, **SHORT), bench, DEVICE, PEAKS,
                            LIMITS)


DRIVE = """
import json, sys
sys.path.insert(0, sys.argv[1])
import harness, run_cell
cell = sys.argv[2]
_, cfg, traffic, bench = harness.find_cell(cell)
result, checks, _ = run_cell.execute(
    cell, 3000000041, 1.0, True, dict(cfg, **json.loads(sys.argv[3])),
    dict(traffic, **json.loads(sys.argv[4])), bench,
    {"platform": "cpu", "kind": "cpu", "count": 1},
    {"flops_per_s": 1e12, "bytes_per_s": 1e11}, harness.cell_limits(cell))
harness.emit(result, checks)
"""


def test_tree_cell_reaches_a_result_line():
    """A traced run in a fresh interpreter ends in the result line, correct,
    with the cell's per-layer metrics read from the program."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([os.path.join(REPO, "src"),
                                           os.environ.get("PYTHONPATH", "")]))
    p = subprocess.run([sys.executable, "-c", DRIVE, BENCH, CELL,
                        json.dumps(TINY), json.dumps(SHORT)], cwd=REPO,
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"], result["checks"]
    assert set(result["checks"]) == {"logit_gap"}
    for m in ("single_pack_ms.schedule", "single_pack_ms.lower",
              "pad_share.trees", "mfu.trees", "queue_wait_p95_ms.trees"):
        assert result["metrics"][m]["value"] > 0, m
    assert result["metrics"]["pad_share.trees"]["value"] < 100


def _swapped_children(orig):
    def merge(reqs):
        from repro.core.graph import Graph, Node

        graph, out_ids = orig(reqs)
        return Graph([Node(id=n.id, type=n.type, op=n.op, attrs=n.attrs,
                           inputs=n.inputs[::-1] if n.type == "I"
                           else n.inputs) for n in graph.nodes]), out_ids
    return merge


def _forget_gate_dropped(hidden: int):
    """The internal cell with the right child's forget term left out:
    c = f_l * c_l + i * u."""
    from repro.core.subgraph import CellProgram

    p = CellProgram("TreeLSTM-Internal")
    hl = p.input("h_l", (hidden,))
    hr = p.input("h_r", (hidden,))
    cl = p.input("c_l", (hidden,))
    p.input("c_r", (hidden,))
    W, b = {}, {}
    for g in ("i", "fl", "fr", "o", "g"):
        W[g] = p.param(f"W{g}", (2 * hidden, hidden))
        b[g] = p.param(f"b{g}", (hidden,))
    hh = p.op("concat2", hl, hr, name="hh")
    y = {g: p.op("affine", hh, W[g], b[g], name=f"y{g}") for g in W}
    i = p.op("sigmoid", y["i"], name="i")
    fl = p.op("sigmoid", y["fl"], name="fl")
    o = p.op("sigmoid", y["o"], name="o")
    g = p.op("tanh", y["g"], name="g")
    t1 = p.op("mul", fl, cl, name="t1")
    t2 = p.op("mul", i, g, name="t2")
    c2 = p.op("add", t1, t2, name="c_out")
    h2 = p.op("mul", o, p.op("tanh", c2, name="tc"), name="h_out")
    p.mark_output(h2, c2)
    return p


def test_tree_fault_children_swapped(monkeypatch):
    import repro.serve.engine as engine

    monkeypatch.setattr(engine, "merge_request_graphs",
                        _swapped_children(engine.merge_request_graphs))
    result, checks, _ = run()
    assert not result["correct"], checks


def test_tree_fault_forget_gate_dropped(monkeypatch):
    import repro.models.trees as trees

    monkeypatch.setattr(trees, "treelstm_internal", _forget_gate_dropped)
    result, checks, _ = run()
    assert not result["correct"], checks


def test_tree_fault_weights_from_another_seed(monkeypatch):
    fam = harness.load_module("families", "tree")
    orig = fam.make_workload
    monkeypatch.setattr(fam, "make_workload", lambda cfg, seed: orig(
        dict(cfg, weight_seed=cfg["weight_seed"] + 1), seed))
    result, checks, _ = run()
    assert not result["correct"], checks


def test_tree_control_fails_at_the_cells_width():
    """A run at the configuration's widths, judged by the rule that decides
    ``correct``: the program passes, and the reference at ``high`` in its
    place, and every leaf's token moved by one id, do not."""
    import control

    result, checks, r = run(widths=None)
    assert result["correct"], checks
    got = control.judged(r, LIMITS)
    assert got["program"]["correct"], got["program"]
    assert not got["control"]["correct"], got["control"]
    assert not got["token_altered"]["correct"], got["token_altered"]
