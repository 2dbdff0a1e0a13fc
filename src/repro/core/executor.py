"""Dynamic-graph batched executor (the DyNet-executor analogue, §4).

Executes a typed dataflow :class:`Graph` whose nodes are cell invocations /
embedding lookups / output projections, following a batch schedule produced
by any batching policy. Per-node outputs live in flat stores, one per field
signature (shape); each batch gathers its inputs by index, runs the node
type's batched implementation once (one "kernel launch"), and scatters the
outputs. Schedules are cached per topology (trace-time scheduling — see
DESIGN.md deviation #2).

Timing is decomposed exactly as the paper's Fig. 8: construction (graph
building, done by the workload), scheduling (batching analysis), and
execution (batched op launches).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.tracer import Tracer, default_tracer

from .batching import (Policy, Schedule, policy_cache_key, resolve_schedule)
from .cache import FIFOCache
from .graph import Graph, TypeId
from .ops import MATMUL_PRECISION


class NodeImpl:
    """Batched implementation of one node type.

    ``out_fields``: names/shapes of the node's output fields.
    ``apply(params, inputs, aux)``: inputs is a list of stacked (k, ...)
    arrays (one per input slot, gathered from predecessor fields);
    ``aux`` is a (k,)-int array of per-node static attributes (token ids).
    Returns dict field -> (k, *shape).

    ``fused_gather`` (optional): a gather-free fast path used by the
    bucketed plan executor — ``fused_gather(params, bufs, idxs, aux,
    interpret=...)`` receives the *source arenas* plus per-slot row-index
    vectors instead of pre-gathered inputs and returns the same output
    dict, letting a Pallas kernel feed the cell math straight from the
    arenas (see ``repro.kernels.fused_gather_cell``).
    """

    def __init__(self, name: str, in_slots: list[tuple[int, str]],
                 out_fields: dict[str, tuple[int, ...]],
                 apply: Callable[..., dict[str, jnp.ndarray]],
                 fused_gather: Callable[..., dict[str, jnp.ndarray]] | None = None):
        self.name = name
        self.in_slots = in_slots          # (pred position, field name)
        self.out_fields = out_fields
        self.apply = apply
        self.fused_gather = fused_gather


@dataclass
class ExecStats:
    n_batches: int = 0
    n_launches: int = 0          # device dispatches (1/run on the plan path)
    n_compiles: int = 0          # distinct XLA compiles (plan paths only)
    schedule_time: float = 0.0
    exec_time: float = 0.0
    lower_time: float = 0.0      # plan lowering + XLA compile (plan path only)


class ExecResult:
    """Per-field flat buffers (n_nodes, *shape) plus lazy per-node access."""

    def __init__(self, graph: Graph, impls, bufs: dict):
        self._graph = graph
        self._impls = impls
        self.bufs = bufs

    def node(self, i: int) -> dict[str, jnp.ndarray]:
        impl = self._impls[self._graph.nodes[i].type]
        out = {}
        for f, shape in impl.out_fields.items():
            out[f] = self.bufs[(f, tuple(shape))][i]
        return out

    def nodes_with_field(self, fld: str):
        for n in self._graph.nodes:
            impl = self._impls.get(n.type)
            if impl and fld in impl.out_fields:
                yield n.id

    def field(self, fld: str, ids) -> jnp.ndarray:
        shapes = set()
        for i in ids:
            impl = self._impls[self._graph.nodes[i].type]
            if fld not in impl.out_fields:
                raise KeyError(f"node {i} ({impl.name}) has no field {fld!r}")
            shapes.add(tuple(impl.out_fields[fld]))
        if len(shapes) != 1:
            raise ValueError(
                f"field {fld!r} has mixed shapes {sorted(shapes)} across the "
                f"requested nodes; select per-shape node subsets instead")
        return self.bufs[(fld, shapes.pop())][np.asarray(ids)]


class DynamicExecutor:
    def __init__(self, impls: dict[TypeId, NodeImpl], params: Any, *,
                 schedule_cache: FIFOCache | None = None,
                 namespace: Any = None, tracer: Tracer | None = None):
        self.impls = impls
        self.params = params
        # FIFO-capped: keys hold policy fingerprints (or references), values
        # whole schedules. A shared cache (serve layer) is namespaced so
        # different impl sets never alias each other's topologies.
        self._schedule_cache = (schedule_cache if schedule_cache is not None
                                else FIFOCache(1024))
        self._ns = namespace
        self.tracer = tracer if tracer is not None else default_tracer()

    def run(self, graph: Graph, policy: Policy | Callable[[Graph], Schedule],
            stats: ExecStats | None = None,
            params: Any = None) -> ExecResult:
        stats = stats if stats is not None else ExecStats()
        t0 = time.perf_counter()
        # "sched" tags the entry kind, so a cache shared with the compiled
        # executors can never hand back (or be handed) the wrong artifact.
        key = ("sched", self._ns, graph.topology_key(),
               policy_cache_key(policy))
        with self.tracer.span("interp.schedule", cat="interp"):
            sched = self._schedule_cache.get(key)
            if sched is None:
                sched = resolve_schedule(graph, policy)
                self._schedule_cache[key] = sched
        stats.schedule_time += time.perf_counter() - t0

        t1 = time.perf_counter()
        params = params if params is not None else self.params
        N = len(graph)
        with self.tracer.span("interp.exec", cat="interp",
                              n_batches=len(sched)):
            # flat per-(field, shape) stores: (n_nodes, *shape) — one gather
            # per input operand and one scatter per output field per batch.
            bufs: dict[tuple, jnp.ndarray] = {}
            nodes = graph.nodes
            for t, ids in sched:
                impl = self.impls[t]
                idx = np.asarray(ids, np.int32)
                inputs = []
                for (slot, fld) in impl.in_slots:
                    src = np.asarray([nodes[i].inputs[slot] for i in ids],
                                     np.int32)
                    shapes = {tuple(self.impls[nodes[p].type].out_fields[fld])
                              for p in src}
                    if len(shapes) != 1:
                        raise ValueError(
                            f"batch of {t!r} slot {slot} field {fld!r} mixes "
                            f"element shapes {sorted(shapes)}; such batches "
                            f"cannot gather from one buffer")
                    inputs.append(bufs[(fld, shapes.pop())][src])
                aux = jnp.asarray(np.asarray(
                    [n.attrs.get("aux", 0) for n in (nodes[i] for i in ids)],
                    np.int32))
                out = impl.apply(params, inputs, aux)
                for f, shape in impl.out_fields.items():
                    k = (f, tuple(shape))
                    if k not in bufs:
                        bufs[k] = jnp.zeros((N,) + tuple(shape), out[f].dtype)
                    bufs[k] = bufs[k].at[idx].set(out[f])
                stats.n_batches += 1
                stats.n_launches += 1
            jax.block_until_ready(list(bufs.values()))
        stats.exec_time += time.perf_counter() - t1
        return ExecResult(graph, self.impls, bufs)


def cell_impl(name: str, compiled_cell, in_slots: list[tuple[int, str]],
              input_names: list[str], pbuf) -> NodeImpl:
    """Wrap a CompiledCell as a NodeImpl: cell inputs come from predecessor
    fields in order; outputs are the cell's outputs."""
    prog = compiled_cell.prog
    # Built once per impl: rebuilding inside apply caused a full retrace of
    # the cell body on every training-mode invocation.
    traced_apply = compiled_cell._build_apply()

    def apply(params, inputs, aux):
        # Threaded params (training) override the baked buffer; executor
        # passes a dict {impl_name: pbuf} or None.
        buf = pbuf
        if isinstance(params, dict) and name in params:
            buf = params[name]
        # Pad the batch to a power-of-two bucket so jit recompiles stay rare.
        k = inputs[0].shape[0] if inputs else int(aux.shape[0])
        kp = 1 << (k - 1).bit_length()
        feed = {}
        for nm, x in zip(input_names, inputs):
            if kp != k:
                pad = [(0, kp - k)] + [(0, 0)] * (x.ndim - 1)
                x = jnp.pad(x, pad)
            feed[nm] = x
        if isinstance(params, dict) and name in params:
            out = traced_apply(buf, feed)  # stay traceable
        else:
            out = compiled_cell.apply(buf, feed)
        if kp != k:
            out = {f: v[:k] for f, v in out.items()}
        return out

    out_fields = {o: prog.vars[o].shape for o in prog.outputs}
    return NodeImpl(name, in_slots, out_fields, apply,
                    fused_gather=_lstm_fused_gather(name, compiled_cell,
                                                    input_names, pbuf))


def _lstm_fused_gather(name: str, compiled_cell, input_names, pbuf):
    """Fused gather→cell fast path for standard LSTM cells, or None.

    Extracts the four gate weight blocks from the cell's packed parameter
    buffer (wherever the PQ plan put them) into the ``(E+H, 4H)``
    gate-blocked layout the fused kernel expects; the concat is traced, so
    XLA folds it for baked params and keeps it differentiable for threaded
    training params.
    """
    prog = compiled_cell.prog
    if prog.name != "LSTMCell" or input_names != ["x", "h", "c"]:
        return None
    E = prog.vars["x"].shape[0]
    H = prog.vars["h"].shape[0]
    w_off = {g: compiled_cell.offsets[f"W{g}"] for g in "ifgo"}
    b_off = {g: compiled_cell.offsets[f"b{g}"] for g in "ifgo"}

    def fused_gather(params, bufs, idxs, aux, interpret=None):
        from repro.kernels.fused_gather_cell import fused_gather_lstm_cell

        buf = pbuf
        if isinstance(params, dict) and name in params:
            buf = params[name]
        w = jnp.concatenate(
            [buf[w_off[g]:w_off[g] + (E + H) * H].reshape(E + H, H)
             for g in "ifgo"], axis=1)
        b = jnp.concatenate([buf[b_off[g]:b_off[g] + H] for g in "ifgo"])
        h2, c2 = fused_gather_lstm_cell(bufs[0], bufs[1], bufs[2],
                                        idxs[0], idxs[1], idxs[2], w, b,
                                        interpret=interpret)
        return {"h_out": h2, "c_out": c2}

    return fused_gather


def embed_impl(name: str, table: jnp.ndarray, field_name: str = "h") -> NodeImpl:
    def apply(params, inputs, aux):
        t = params[name] if isinstance(params, dict) and name in params else table
        return {field_name: t[aux]}
    return NodeImpl(name, [], {field_name: (table.shape[1],)}, apply)


def affine_impl(name: str, w: jnp.ndarray, b: jnp.ndarray,
                in_field: str = "h", out_field: str = "h") -> NodeImpl:
    def apply(params, inputs, aux):
        return {out_field: jnp.matmul(inputs[0], w,
                                      precision=MATMUL_PRECISION) + b}
    return NodeImpl(name, [(0, in_field)], {out_field: (w.shape[1],)}, apply)
