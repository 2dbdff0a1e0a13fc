"""Tree-based workloads: TreeLSTM, TreeGRU, MV-RNN, TreeLSTM-2Type.

Graphs follow Fig. 1: leaf embed nodes (E), leaf cells (L), internal cells
(I / I2), and a per-node output head (O) — the structure whose O nodes the
depth/agenda heuristics scatter across batches but the FSM executes in one.
"""

from __future__ import annotations

import random

import jax.numpy as jnp
import numpy as np

from repro.core.executor import NodeImpl, cell_impl, embed_impl
from repro.core.graph import Graph, Node
from repro.core.ops import MATMUL_PRECISION
from repro.core.subgraph import CompiledCell
from .cells import (gru_cell, mv_cell, treegru_internal, treegru_leaf,
                    treelstm_internal, treelstm_leaf)
from .data import TreeNode, random_tree

N_CLASSES = 5
VOCAB = 1000


def _tree_graph(trees: list[TreeNode], internal_types: int = 1,
                with_c: bool = True) -> Graph:
    nodes: list[Node] = []

    def add(type_, inputs=(), aux=0):
        nodes.append(Node(id=len(nodes), type=type_, inputs=tuple(inputs),
                          attrs={"aux": aux}))
        return len(nodes) - 1

    def visit(t: TreeNode) -> int:
        if t.is_leaf:
            e = add("E", aux=t.token)
            cell = add("L", (e,))
        else:
            l = visit(t.left)
            r = visit(t.right)
            ty = "I" if internal_types == 1 else f"I{t.tag + 1}"
            cell = add(ty, (l, r))
        add("O", (cell,))
        return cell

    for t in trees:
        visit(t)
    return Graph(nodes)


def _out_impl(rng: np.random.Generator, hidden: int) -> NodeImpl:
    w = jnp.asarray(0.1 * rng.standard_normal((hidden, N_CLASSES)), jnp.float32)
    b = jnp.zeros(N_CLASSES, jnp.float32)

    def apply(params, inputs, aux):
        return {"y": jnp.matmul(inputs[0], w, precision=MATMUL_PRECISION) + b}

    return NodeImpl("O", [(0, "h")], {"y": (N_CLASSES,)}, apply)


class TreeWorkload:
    """name in {TreeLSTM, TreeGRU, MV-RNN, TreeLSTM-2Type}."""

    def __init__(self, name: str, model_size: int = 64, seed: int = 0,
                 layout: str = "planned"):
        self.name = name
        self.model_size = model_size
        self.layout = layout
        rng = np.random.default_rng(seed)
        h = model_size
        self.impls: dict = {}
        if name in ("TreeLSTM", "TreeLSTM-2Type"):
            leaf = CompiledCell(treelstm_leaf(h, h), layout)
            table = jnp.asarray(0.1 * rng.standard_normal((VOCAB, h)), jnp.float32)
            self.impls["E"] = embed_impl("E", table, "x")
            self.impls["L"] = cell_impl("L", leaf, [(0, "x")], ["x"],
                                        leaf.init_params(rng))
            n_int = 2 if name == "TreeLSTM-2Type" else 1
            for k in range(n_int):
                internal = CompiledCell(treelstm_internal(h), layout)
                ty = "I" if n_int == 1 else f"I{k + 1}"
                self.impls[ty] = cell_impl(
                    ty, internal, [(0, "h_out"), (1, "h_out"), (0, "c_out"), (1, "c_out")],
                    ["h_l", "h_r", "c_l", "c_r"], internal.init_params(rng))
            self._h_field = "h_out"
            self.cells = {"TreeLSTM-Leaf": leaf, "TreeLSTM-Internal": internal}
        elif name == "TreeGRU":
            leaf = CompiledCell(treegru_leaf(h, h), layout)
            internal = CompiledCell(treegru_internal(h), layout)
            table = jnp.asarray(0.1 * rng.standard_normal((VOCAB, h)), jnp.float32)
            self.impls["E"] = embed_impl("E", table, "x")
            self.impls["L"] = cell_impl("L", leaf, [(0, "x")], ["x"],
                                        leaf.init_params(rng))
            self.impls["I"] = cell_impl("I", internal,
                                        [(0, "h_out"), (1, "h_out")],
                                        ["h_l", "h_r"], internal.init_params(rng))
            self._h_field = "h_out"
            self.cells = {"TreeGRU-Leaf": leaf, "TreeGRU-Internal": internal}
        elif name == "MV-RNN":
            internal = CompiledCell(mv_cell(h), layout)
            vec = jnp.asarray(0.1 * rng.standard_normal((VOCAB, h)), jnp.float32)
            mat = jnp.asarray(
                np.broadcast_to(np.eye(h, dtype=np.float32), (VOCAB, h, h))
                + 0.02 * rng.standard_normal((VOCAB, h, h)), jnp.float32)

            def embed_apply(params, inputs, aux):
                return {"a_out": vec[aux], "A_out": mat[aux]}

            # Leaves feed the same fields internal nodes produce.
            self.impls["E"] = NodeImpl("E", [], {"a_out": (h,), "A_out": (h, h)},
                                       embed_apply)
            self.impls["L"] = None  # MV-RNN has no separate leaf cell
            self.impls["I"] = cell_impl(
                "I", internal,
                [(0, "a_out"), (1, "a_out"), (0, "A_out"), (1, "A_out")],
                ["a_l", "a_r", "A_l", "A_r"], internal.init_params(rng))
            self._h_field = "a_out"
            self.cells = {"MVCell": internal}
        else:
            raise ValueError(name)
        # Output head reads the h-like field.
        out = _out_impl(rng, h)
        out.in_slots = [(0, self._h_field)]
        self.impls["O"] = out
        self.impls = {k: v for k, v in self.impls.items() if v is not None}

    def sample_graph(self, rng: random.Random, batch_size: int,
                     leaves_lo: int = 6, leaves_hi: int = 18) -> Graph:
        n_tags = 2 if self.name == "TreeLSTM-2Type" else 1
        trees = [random_tree(rng, rng.randint(leaves_lo, leaves_hi),
                             VOCAB, n_tags) for _ in range(batch_size)]
        if self.name == "MV-RNN":
            return _mvrnn_graph(trees)
        return _tree_graph(trees, internal_types=n_tags)


def _mvrnn_graph(trees: list[TreeNode]) -> Graph:
    nodes: list[Node] = []

    def add(type_, inputs=(), aux=0):
        nodes.append(Node(id=len(nodes), type=type_, inputs=tuple(inputs),
                          attrs={"aux": aux}))
        return len(nodes) - 1

    def visit(t: TreeNode) -> int:
        if t.is_leaf:
            cell = add("E", aux=t.token)
        else:
            l = visit(t.left)
            r = visit(t.right)
            cell = add("I", (l, r))
        add("O", (cell,))
        return cell

    for t in trees:
        visit(t)
    return Graph(nodes)
