"""The comparison that decides ``correct``, driven through a whole run at a
tiny size on the CPU (the chip check skipped), with the timed path broken
underneath: each fault a cell can have must come out not correct, and the
unbroken run correct. The control, the reference at the next lower
precision, must fail the cell's limits at the configuration's own width."""

import chipbench_paths  # noqa: F401  (first: the path to the benchmark)

import os

import pytest

import harness
import run_cell

CELL = "lstmlm-ptb-medium.decode-sat"
LIMITS = harness.cell_limits(CELL)
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
PEAKS = {"flops_per_s": 1e12, "bytes_per_s": 1e11}
# Few short requests on few slots, so a CPU run holds them.
SHORT = {"clients": 8, "prompt_len": [3, 5, 7, 9], "max_new": [4, 6, 8, 10],
         "setup_turnovers": 1, "check_finished": 4,
         "stagger": {"prompt_len": 4, "span_rounds": 12, "group_sizes": [2]}}


def run(width: int | None = 32, seconds: float = 1.0):
    """A whole run of the cell on the CPU with its traffic shortened; at a
    tiny width, or at the configuration's own with ``width=None``."""
    _, cfg, traffic, bench = harness.find_cell(CELL)
    cfg = dict(cfg, max_slots=8)
    if width is not None:
        cfg.update(embed=width, hidden=width, vocab=64)
    traffic = dict(traffic, **SHORT)
    return run_cell.execute(CELL, 3000000007, seconds, False, cfg, traffic,
                            bench, DEVICE, PEAKS, LIMITS)


def _state_unchanged(orig):
    def commit(y_arena, y_rows, slots, state_arenas, state_rows, pools):
        toks, _ = orig(y_arena, y_rows, slots, state_arenas, state_rows,
                       pools)
        return toks, pools
    return commit


def _half_left_out(orig):
    def commit(y_arena, y_rows, slots, state_arenas, state_rows, pools):
        toks, new = orig(y_arena, y_rows, slots, state_arenas, state_rows,
                         pools)
        rest = slots[len(slots) // 2:]
        return toks, [n.at[rest].set(p[rest]) for n, p in zip(new, pools)]
    return commit


def _token_altered(orig):
    def commit(y_arena, y_rows, slots, state_arenas, state_rows, pools):
        toks, new = orig(y_arena, y_rows, slots, state_arenas, state_rows,
                         pools)
        return (toks + 1) % y_arena.shape[-1], new
    return commit


def test_lm_unbroken_run_is_correct():
    result, checks, _ = run()
    assert result["correct"], checks


@pytest.mark.parametrize("fault", [_state_unchanged, _half_left_out,
                                   _token_altered])
def test_lm_fault_is_not_correct(monkeypatch, fault):
    import repro.serve.engine as engine

    monkeypatch.setattr(engine, "_fused_commit",
                        fault(engine._fused_commit))
    result, checks, _ = run()
    assert not result["correct"], checks


def test_lm_control_fails_at_the_cells_width():
    """A run of the cell at its configuration's widths, judged by the rule
    that decides ``correct``: the program passes, and the reference at
    ``high`` in its place, and a token altered, do not."""
    import control

    result, checks, r = run(width=None)
    assert result["correct"], checks
    got = control.judged(r, LIMITS)
    assert got["program"]["correct"], got["program"]
    assert not got["control"]["correct"], got["control"]
    assert not got["token_altered"]["correct"], got["token_altered"]
    assert got["control"]["step_rms"] > LIMITS["step_rms"]
