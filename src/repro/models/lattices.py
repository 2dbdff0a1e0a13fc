"""Lattice-based workloads: LatticeLSTM (Chinese NER) and LatticeGRU (NMT).

Topology per Fig. 7: a chain of character cells with word-cell jump links.
A word cell W(i, j) reads the char state at i and merges into the char cell
at j+1 (type CW). The FSM policy learns to run all char cells of a wave
first and delay word cells — the depth/agenda heuristics interleave them
arbitrarily, costing up to 3.27x more batches (Fig. 9).
"""

from __future__ import annotations

import random

import jax.numpy as jnp
import numpy as np

from repro.core.executor import NodeImpl, cell_impl, embed_impl
from repro.core.graph import Graph, Node
from repro.core.ops import MATMUL_PRECISION
from repro.core.subgraph import CompiledCell
from .cells import gru_cell, lattice_char_gru, lattice_char_lstm, lstm_cell
from .data import random_lattice

CHAR_VOCAB = 1000
WORD_VOCAB = 5000
N_TAGS = 9


class LatticeLSTM:
    name = "LatticeLSTM"

    def __init__(self, model_size: int = 64, seed: int = 0,
                 layout: str = "planned"):
        rng = np.random.default_rng(seed)
        h = model_size
        self.model_size = h
        char = CompiledCell(lstm_cell(h, h), layout)
        charw = CompiledCell(lattice_char_lstm(h, h), layout)
        word = CompiledCell(lstm_cell(h, h), layout)
        ctab = jnp.asarray(0.1 * rng.standard_normal((CHAR_VOCAB, h)), jnp.float32)
        wtab = jnp.asarray(0.1 * rng.standard_normal((WORD_VOCAB, h)), jnp.float32)
        wo = jnp.asarray(0.1 * rng.standard_normal((h, N_TAGS)), jnp.float32)

        def out_apply(params, inputs, aux):
            return {"y": jnp.matmul(inputs[0], wo, precision=MATMUL_PRECISION)}

        def zero_apply(params, inputs, aux):
            z = jnp.zeros((aux.shape[0], h), jnp.float32)
            return {"h_out": z, "c_out": z}

        self.impls = {
            "EC": embed_impl("EC", ctab, "x"),
            "EW": embed_impl("EW", wtab, "x"),
            "S": NodeImpl("S", [], {"h_out": (h,), "c_out": (h,)}, zero_apply),
            "C": cell_impl("C", char, [(1, "x"), (0, "h_out"), (0, "c_out")],
                           ["x", "h", "c"], char.init_params(rng)),
            # CW: (prev char cell, char embed, word cell)
            "CW": cell_impl("CW", charw,
                            [(1, "x"), (0, "h_out"), (0, "c_out"),
                             (2, "h_out"), (2, "c_out")],
                            ["x", "h", "c", "h_w", "c_w"], charw.init_params(rng)),
            # W: (char cell at word start, word embed)
            "W": cell_impl("W", word, [(1, "x"), (0, "h_out"), (0, "c_out")],
                           ["x", "h", "c"], word.init_params(rng)),
            "O": NodeImpl("O", [(0, "h_out")], {"y": (N_TAGS,)}, out_apply),
        }
        self.cells = {"LSTMCell": char, "LatticeCharLSTM": charw}

    def sample_graph(self, rng: random.Random, batch_size: int,
                     lo: int = 10, hi: int = 26) -> Graph:
        nodes: list[Node] = []

        def add(type_, inputs=(), aux=0):
            nodes.append(Node(id=len(nodes), type=type_, inputs=tuple(inputs),
                              attrs={"aux": aux}))
            return len(nodes) - 1

        for _ in range(batch_size):
            lat = random_lattice(rng, lo, hi, CHAR_VOCAB, WORD_VOCAB)
            prev = add("S")
            char_cells: list[int] = []
            pending_word: int | None = None
            for j, tok in enumerate(lat.chars):
                e = add("EC", aux=tok)
                if pending_word is not None:
                    cell = add("CW", (prev, e, pending_word))
                    pending_word = None
                else:
                    cell = add("C", (prev, e))
                char_cells.append(cell)
                add("O", (cell,))
                w = lat.words[j]
                if w is not None:
                    start, wtok = w
                    ew = add("EW", aux=wtok)
                    pending_word = add("W", (char_cells[start], ew))
                prev = cell
        return Graph(nodes)


class LatticeGRU:
    name = "LatticeGRU"

    def __init__(self, model_size: int = 64, seed: int = 0,
                 layout: str = "planned"):
        rng = np.random.default_rng(seed)
        h = model_size
        self.model_size = h
        char = CompiledCell(gru_cell(h, h), layout)
        charw = CompiledCell(lattice_char_gru(h, h), layout)
        word = CompiledCell(gru_cell(h, h), layout)
        ctab = jnp.asarray(0.1 * rng.standard_normal((CHAR_VOCAB, h)), jnp.float32)
        wtab = jnp.asarray(0.1 * rng.standard_normal((WORD_VOCAB, h)), jnp.float32)
        wo = jnp.asarray(0.1 * rng.standard_normal((h, N_TAGS)), jnp.float32)

        def out_apply(params, inputs, aux):
            return {"y": jnp.matmul(inputs[0], wo, precision=MATMUL_PRECISION)}

        def zero_apply(params, inputs, aux):
            return {"h_out": jnp.zeros((aux.shape[0], h), jnp.float32)}

        self.impls = {
            "EC": embed_impl("EC", ctab, "x"),
            "EW": embed_impl("EW", wtab, "x"),
            "S": NodeImpl("S", [], {"h_out": (h,)}, zero_apply),
            "C": cell_impl("C", char, [(1, "x"), (0, "h_out")],
                           ["x", "h"], char.init_params(rng)),
            "CW": cell_impl("CW", charw,
                            [(1, "x"), (0, "h_out"), (2, "h_out")],
                            ["x", "h", "h_w"], charw.init_params(rng)),
            "W": cell_impl("W", word, [(1, "x"), (0, "h_out")],
                           ["x", "h"], word.init_params(rng)),
            "O": NodeImpl("O", [(0, "h_out")], {"y": (N_TAGS,)}, out_apply),
        }
        self.cells = {"GRUCell": char, "LatticeCharGRU": charw}

    def sample_graph(self, rng: random.Random, batch_size: int,
                     lo: int = 10, hi: int = 26) -> Graph:
        nodes: list[Node] = []

        def add(type_, inputs=(), aux=0):
            nodes.append(Node(id=len(nodes), type=type_, inputs=tuple(inputs),
                              attrs={"aux": aux}))
            return len(nodes) - 1

        for _ in range(batch_size):
            lat = random_lattice(rng, lo, hi, CHAR_VOCAB, WORD_VOCAB)
            prev = add("S")
            char_cells: list[int] = []
            pending_word: int | None = None
            for j, tok in enumerate(lat.chars):
                e = add("EC", aux=tok)
                if pending_word is not None:
                    cell = add("CW", (prev, e, pending_word))
                    pending_word = None
                else:
                    cell = add("C", (prev, e))
                char_cells.append(cell)
                add("O", (cell,))
                w = lat.words[j]
                if w is not None:
                    start, wtok = w
                    ew = add("EW", aux=wtok)
                    pending_word = add("W", (char_cells[start], ew))
                prev = cell
        return Graph(nodes)
