"""Operation counts of the chain LM against hand counts."""

import chipbench_paths  # noqa: F401  (first: the path to the benchmark)

import pytest

from harness import load_module

lstm = load_module("counts", "lstm")


@pytest.mark.parametrize("embed,hidden,want", [
    # E = H = 2: a (1 x 4) by (4 x 8) gate matmul is 64 operations, the
    # bias 8, the state update 9 per hidden unit.
    (2, 2, 64 + 8 + 18),
    # The configuration's widths, E = H = 650: (1 x 1300) by (1300 x 2600)
    # is 6,760,000, the bias 2,600, the state update 5,850.
    (650, 650, 6_760_000 + 2_600 + 5_850),
])
def test_lstm_cell_by_hand(embed, hidden, want):
    assert lstm.cell_flops(embed, hidden) == want


@pytest.mark.parametrize("hidden,vocab,want", [
    (2, 3, 12 + 3),                      # (1 x 2) by (2 x 3), bias 3
    (650, 10000, 13_000_000 + 10_000),   # the 10k-word head
])
def test_head_by_hand(hidden, vocab, want):
    assert lstm.head_flops(hidden, vocab) == want
