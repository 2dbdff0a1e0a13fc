"""Operations of the chain LM, from shapes alone.

A matmul of (m, k) by (k, n) is 2 m k n operations; each elementwise
sigmoid, tanh, multiply or add is one operation per element.
"""

from __future__ import annotations


def cell_flops(embed: int, hidden: int) -> int:
    """One LSTM cell on one row: the (E+H) x 4H gate matmul and its bias,
    then the state update (3 sigmoids, 2 tanh, 3 multiplies and one add
    per hidden unit, each counted as one operation)."""
    return 2 * (embed + hidden) * 4 * hidden + 4 * hidden + 9 * hidden


def head_flops(hidden: int, vocab: int) -> int:
    return 2 * hidden * vocab + vocab

