"""Chain-based workloads: BiLSTM-Tagger and LSTM-NMT.

Chain topologies are the easy case (both the agenda heuristic and the FSM
find the optimal policy, §5.2); the speedup there comes from the PQ-planned
cells. We build them faithfully anyway — they are the paper's baselines.
"""

from __future__ import annotations

import random

import jax.numpy as jnp
import numpy as np

from repro.core.executor import NodeImpl, cell_impl, embed_impl
from repro.core.graph import Graph, Node
from repro.core.ops import MATMUL_PRECISION
from repro.core.subgraph import CompiledCell
from .cells import lstm_cell
from .data import random_sentence

VOCAB = 1000
N_TAGS = 17
OUT_VOCAB = 500


def _zero_state_impl(hidden: int) -> NodeImpl:
    def apply(params, inputs, aux):
        k = aux.shape[0]
        z = jnp.zeros((k, hidden), jnp.float32)
        return {"h_out": z, "c_out": z}
    return NodeImpl("S", [], {"h_out": (hidden,), "c_out": (hidden,)}, apply)


class BiLSTMTagger:
    name = "BiLSTM-Tagger"

    def __init__(self, model_size: int = 64, seed: int = 0,
                 layout: str = "planned"):
        rng = np.random.default_rng(seed)
        h = model_size
        self.model_size = h
        fwd = CompiledCell(lstm_cell(h, h), layout)
        bwd = CompiledCell(lstm_cell(h, h), layout)
        table = jnp.asarray(0.1 * rng.standard_normal((VOCAB, h)), jnp.float32)
        wo = jnp.asarray(0.1 * rng.standard_normal((2 * h, N_TAGS)), jnp.float32)
        bo = jnp.zeros(N_TAGS, jnp.float32)

        def out_apply(params, inputs, aux):
            return {"y": jnp.matmul(jnp.concatenate(inputs, axis=-1), wo,
                                    precision=MATMUL_PRECISION) + bo}

        self.impls = {
            "E": embed_impl("E", table, "x"),
            "S": _zero_state_impl(h),
            "F": cell_impl("F", fwd, [(1, "x"), (0, "h_out"), (0, "c_out")],
                           ["x", "h", "c"], fwd.init_params(rng)),
            "B": cell_impl("B", bwd, [(1, "x"), (0, "h_out"), (0, "c_out")],
                           ["x", "h", "c"], bwd.init_params(rng)),
            "O": NodeImpl("O", [(0, "h_out"), (1, "h_out")], {"y": (N_TAGS,)},
                          out_apply),
        }
        self.cells = {"LSTMCell": fwd}

    def sample_graph(self, rng: random.Random, batch_size: int,
                     lo: int = 8, hi: int = 24) -> Graph:
        nodes: list[Node] = []

        def add(type_, inputs=(), aux=0):
            nodes.append(Node(id=len(nodes), type=type_, inputs=tuple(inputs),
                              attrs={"aux": aux}))
            return len(nodes) - 1

        for _ in range(batch_size):
            sent = random_sentence(rng, lo, hi, VOCAB)
            embeds = [add("E", aux=t) for t in sent]
            s_f = add("S")
            s_b = add("S")
            fs = []
            prev = s_f
            for e in embeds:
                prev = add("F", (prev, e))
                fs.append(prev)
            bs = []
            prev = s_b
            for e in reversed(embeds):
                prev = add("B", (prev, e))
                bs.append(prev)
            bs.reverse()
            for f, b2 in zip(fs, bs):
                add("O", (f, b2))
        return Graph(nodes)


class ChainLM:
    """Autoregressive chain LM — the servable "chain LM decode" family.

    A language model as a dynamic dataflow graph: prefill is a chain of
    LSTM cells over the prompt tokens, decode is one cell per generated
    token. Unlike the offline workloads, generation is closed-loop (the
    next embed's ``aux`` is the argmax of the previous logits), so the
    serve engine executes one *round graph* per decode step and carries
    per-request recurrent state across rounds in a slot pool:

    - ``R`` (resume) nodes read a request's ``(h, c)`` out of the pool,
      indexed by slot id in ``aux``. The pool is threaded through executor
      ``params`` (key ``"slots"``), never baked into a compiled plan, so
      one AOT executable serves every round.
    - After a round the engine scatters each live request's last cell state
      back into its slot.

    Round-graph topology depends only on the number of prefill chains per
    length bucket and the (padded) decode count — token ids and slot ids
    are ``aux`` data — so recurring traffic shapes hit the per-topology
    schedule/plan caches.
    """

    name = "ChainLM"
    state_fields = ("h_out", "c_out")

    def __init__(self, model_size: int = 64, seed: int = 0,
                 layout: str = "planned", vocab: int = 256):
        rng = np.random.default_rng(seed)
        h = model_size
        self.model_size = h
        self.vocab = vocab
        dec = CompiledCell(lstm_cell(h, h), layout)
        table = jnp.asarray(0.1 * rng.standard_normal((vocab, h)), jnp.float32)
        wo = jnp.asarray(0.1 * rng.standard_normal((h, vocab)), jnp.float32)
        bo = jnp.zeros(vocab, jnp.float32)

        def out_apply(params, inputs, aux):
            return {"y": jnp.matmul(inputs[0], wo,
                                    precision=MATMUL_PRECISION) + bo}

        def slot_apply(params, inputs, aux):
            slots = params["slots"]       # engine-threaded, (max_slots, h)
            return {f: slots[f][aux] for f in ChainLM.state_fields}

        self.impls = {
            "E": embed_impl("E", table, "x"),
            "S": _zero_state_impl(h),
            "R": NodeImpl("R", [], {"h_out": (h,), "c_out": (h,)}, slot_apply),
            "C": cell_impl("C", dec, [(1, "x"), (0, "h_out"), (0, "c_out")],
                           ["x", "h", "c"], dec.init_params(rng)),
            "O": NodeImpl("O", [(0, "h_out")], {"y": (vocab,)}, out_apply),
        }
        self.cells = {"LSTMCell": dec}

    def init_slots(self, n_slots: int) -> dict[str, jnp.ndarray]:
        return {f: jnp.zeros((n_slots, self.model_size), jnp.float32)
                for f in self.state_fields}

    def sample_graph(self, rng: random.Random, batch_size: int,
                     lo: int = 4, hi: int = 16) -> Graph:
        """Offline view (scoring a known token sequence), for RL training:
        same types the serve rounds use, S -> (E, C)* -> O per sequence."""
        nodes: list[Node] = []

        def add(type_, inputs=(), aux=0):
            nodes.append(Node(id=len(nodes), type=type_, inputs=tuple(inputs),
                              attrs={"aux": aux}))
            return len(nodes) - 1

        for _ in range(batch_size):
            toks = random_sentence(rng, lo, hi, self.vocab)
            prev = add("S")
            for t in toks:
                e = add("E", aux=t)
                prev = add("C", (prev, e))
                add("O", (prev,))
        return Graph(nodes)


class LSTMNMT:
    name = "LSTM-NMT"

    def __init__(self, model_size: int = 64, seed: int = 0,
                 layout: str = "planned"):
        rng = np.random.default_rng(seed)
        h = model_size
        self.model_size = h
        enc = CompiledCell(lstm_cell(h, h), layout)
        dec = CompiledCell(lstm_cell(h, h), layout)
        src_table = jnp.asarray(0.1 * rng.standard_normal((VOCAB, h)), jnp.float32)
        tgt_table = jnp.asarray(0.1 * rng.standard_normal((OUT_VOCAB, h)), jnp.float32)
        wo = jnp.asarray(0.1 * rng.standard_normal((h, OUT_VOCAB)), jnp.float32)
        bo = jnp.zeros(OUT_VOCAB, jnp.float32)

        def out_apply(params, inputs, aux):
            return {"y": jnp.matmul(inputs[0], wo,
                                    precision=MATMUL_PRECISION) + bo}

        self.impls = {
            "Es": embed_impl("Es", src_table, "x"),
            "Et": embed_impl("Et", tgt_table, "x"),
            "S": _zero_state_impl(h),
            "ENC": cell_impl("ENC", enc, [(1, "x"), (0, "h_out"), (0, "c_out")],
                             ["x", "h", "c"], enc.init_params(rng)),
            "DEC": cell_impl("DEC", dec, [(1, "x"), (0, "h_out"), (0, "c_out")],
                             ["x", "h", "c"], dec.init_params(rng)),
            "O": NodeImpl("O", [(0, "h_out")], {"y": (OUT_VOCAB,)}, out_apply),
        }
        self.cells = {"LSTMCell": enc}

    def sample_graph(self, rng: random.Random, batch_size: int,
                     lo: int = 8, hi: int = 20) -> Graph:
        nodes: list[Node] = []

        def add(type_, inputs=(), aux=0):
            nodes.append(Node(id=len(nodes), type=type_, inputs=tuple(inputs),
                              attrs={"aux": aux}))
            return len(nodes) - 1

        for _ in range(batch_size):
            src = random_sentence(rng, lo, hi, VOCAB)
            tgt = random_sentence(rng, lo, hi, OUT_VOCAB)
            prev = add("S")
            for t in src:
                e = add("Es", aux=t)
                prev = add("ENC", (prev, e))
            for t in [0] + tgt[:-1]:  # teacher forcing from BOS
                e = add("Et", aux=t)
                prev = add("DEC", (prev, e))
                add("O", (prev,))
        return Graph(nodes)
