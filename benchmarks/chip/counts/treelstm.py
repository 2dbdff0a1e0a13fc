"""Operations of the binary Tree-LSTM with a head at every node, from
shapes alone.

A matmul of (m, k) by (k, n) is 2 m k n operations; each elementwise
sigmoid, tanh, multiply or add is one operation per element.
"""

from __future__ import annotations


def leaf_flops(embed: int, hidden: int) -> int:
    """One leaf: three (E x H) gate matmuls and their biases, then 2
    sigmoids, 2 tanh and 2 multiplies per hidden unit."""
    return 3 * (2 * embed * hidden + hidden) + 6 * hidden


def internal_flops(hidden: int) -> int:
    """One internal node: five (2H x H) gate matmuls and their biases, then
    4 sigmoids, 2 tanh, 4 multiplies and 2 adds per hidden unit."""
    return 5 * (2 * 2 * hidden * hidden + hidden) + 12 * hidden


def head_flops(hidden: int, classes: int) -> int:
    return 2 * hidden * classes + classes


def tree_flops(leaves: int, internal: int, embed: int, hidden: int,
               classes: int) -> int:
    """Real nodes only: every leaf and internal cell, and the head at each
    of them (the embedding lookup is a read, not an operation)."""
    return (leaves * leaf_flops(embed, hidden)
            + internal * internal_flops(hidden)
            + (leaves + internal) * head_flops(hidden, classes))
