"""Compiled execution plans (DESIGN.md §2.3, deviations #3 and #4).

The interpreted :class:`~repro.core.executor.DynamicExecutor` re-walks its
cached schedule in Python on every run — one jit dispatch, one numpy gather
per operand, and one scatter into a freshly zeroed full-size buffer per
batch.  This module lowers a cached ``(Schedule, memory plan)`` pair into a
*static execution plan* that removes all of that overhead, at two levels of
specialization:

- **Arenas.**  Every node output lives in a per-``(field, elem_shape)``
  arena of shape ``(rows, *elem_shape)``.  Row assignment is the memory
  plan: the PQ-tree planner (:mod:`repro.core.memplan`) runs once per
  topology over the schedule's batches — each batch contributes its result
  and source operands as adjacency + alignment constraints — so planned
  operands occupy ascending contiguous row runs.  Universes beyond
  ``max_pq_vars`` are planned in chunks (``memplan.plan_rows_chunked``)
  instead of silently skipping the planner.

- **Per-topology plans** (:class:`CompiledPlan`, deviation #3).  Every
  batch's gather/scatter index vectors are baked in as trace-time
  constants: contiguous runs lower to static ``lax.slice`` /
  ``lax.dynamic_update_slice``, duplicated sources to broadcasts, the rest
  to :func:`repro.kernels.gather_batch.gather_rows`.  Fastest per run, but
  every distinct topology pays a fresh XLA compile.

- **Bucketed plan families** (:class:`BucketedPlanExecutor`, deviation #4).
  Index vectors, aux ids, and step activity enter the jitted program as
  *runtime operands*; batch widths, same-type step runs, and arena rows are
  padded up to bucket boundaries (powers of two by default, or a configured
  ladder).  One compiled executable serves every topology whose padded
  shape — the :class:`BucketSpec` — matches; a new topology costs host-side
  index packing only.  Inactive pad lanes/steps are masked by index
  redirection: their reads replicate real rows and their writes land on a
  reserved trash row, so no explicit select enters the program.  Steps
  whose impl exposes a ``fused_gather`` path run the fused Pallas
  gather→cell kernel (:mod:`repro.kernels.fused_gather_cell`) straight off
  the arenas instead of materializing gathered operands.  An optional
  commit stage (:class:`CommitSpec`) runs after the last step: it reads
  each fragment's output and state rows and returns tokens and updated
  state pools from the same program, so a recurrent serve round is one
  dispatch.

- **Sharded bucketed execution** (:class:`ShardedBucketedPlanExecutor`).
  K shards' runtime operands — index packs, aux vectors, arena pools,
  per-shard params such as serve slot pools — stack on a leading device
  axis and the same bucket program runs under ``jax.shard_map`` over a 1-D
  ``("data",)`` mesh: one executable, one dispatch, K data-parallel
  replicas.  Bucket signatures carry the shard count
  (``BucketSpec.n_shards``), so the executable cache and persistent XLA
  cache key sharded builds apart from single-device ones with no new
  machinery.

Both compiled paths execute as one ``jax.jit`` dispatch per run.  The
interpreted executor remains the reference path; the equivalence suites in
``tests/test_plan.py``, ``tests/test_bucketed.py``, and
``tests/test_sharded.py`` pin them together numerically.
"""

from __future__ import annotations

import hashlib
import time
import warnings
from dataclasses import dataclass, replace
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.obs.tracer import Tracer, default_tracer

from . import memplan
from .batching import Policy, Schedule, policy_cache_key, resolve_schedule
from .cache import FIFOCache, LRUCache
from .executor import ExecStats, NodeImpl
from .graph import Graph, TypeId

ArenaKey = tuple[str, tuple[int, ...]]  # (field name, element shape)

SLICE, GATHER, BROADCAST, SCATTER = "slice", "gather", "broadcast", "scatter"


def _sig_digest(obj: Any) -> str:
    """Short stable digest of a cache key / bucket signature — the value
    ``xla.compile`` trace spans carry so a compile wall can be attributed
    to a specific bucket signature across runs and dumps."""
    return hashlib.sha1(repr(obj).encode()).hexdigest()[:12]


# Public alias: serve-layer checkpointing keys quarantine entries by the same
# digest the tracer stamps on spans, so a serialized table stays attributable.
sig_digest = _sig_digest


def _call_compile_hook(hook: Callable, key: Any, ctx: dict) -> None:
    """Invoke a compile hook with the executable-cache key and, when the
    hook accepts it, a job-context dict (kind, signature digest, whether the
    build runs on a background compile worker). Single-argument hooks from
    before the async compile service keep working unchanged."""
    try:
        n_pos = _hook_arity(hook)
    except (TypeError, ValueError):
        n_pos = 1
    if n_pos >= 2:
        hook(key, ctx)
    else:
        hook(key)


def _hook_arity(hook: Callable) -> int:
    import inspect

    sig = inspect.signature(hook)
    n = 0
    for p in sig.parameters.values():
        if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD):
            n += 1
        elif p.kind == p.VAR_POSITIONAL:
            return 2
    return n


def bucket_up(n: int, ladder: tuple[int, ...] | None = None) -> int:
    """Smallest bucket >= n: next power of two, or the first rung of a
    configured ladder (falling back to powers of two past its top). A
    ladder's first rung is a floor — ``bucket_up(1, (8,)) == 8`` — which is
    how serving collapses all small widths onto one executable."""
    if ladder:
        for b in ladder:
            if b >= n:
                return int(b)
    if n <= 1:
        return 1
    return 1 << (n - 1).bit_length()


@dataclass(frozen=True)
class LoweredOperand:
    """One batch operand, resolved to arena rows at plan-compile time."""

    arena: ArenaKey
    mode: str                 # slice | gather | broadcast (reads); slice | scatter (writes)
    start: int = 0            # slice / broadcast: first row
    rows: tuple[int, ...] = ()  # gather / scatter: row per batch element


@dataclass(frozen=True)
class LoweredStep:
    """One schedule batch in canonical element order."""

    type: TypeId
    ids: tuple[int, ...]      # node ids, ordered by primary-output arena row
    k: int
    aux_start: int            # offset into the flat aux vector
    inputs: tuple[LoweredOperand, ...]
    outputs: tuple[tuple[str, LoweredOperand], ...]  # (field, write op)


@dataclass
class PlanStats:
    """Lowering outcome — the Table 2-style data-movement decomposition."""

    n_steps: int = 0
    n_arenas: int = 0
    layout: str = "schedule"        # "pq" | "pq-chunked" | "schedule"
    n_slice_reads: int = 0
    n_gather_reads: int = 0
    n_broadcast_reads: int = 0
    n_slice_writes: int = 0
    n_scatter_writes: int = 0
    n_gather_fallback_steps: int = 0  # steps with >= 1 gathered/scattered operand
    n_pq_planned_batches: int = 0     # batches the PQ pipeline kept zero-copy
    n_pq_erased_batches: int = 0
    n_pq_chunks: int = 0              # > 1 when the chunked planner ran
    pq_skipped: str = ""              # non-empty: PQ pipeline skipped (+ why)
    bucketed: bool = False            # lowered for the bucketed executor
    n_pad_steps: int = 0              # inactive steps added by run padding
    n_real_lanes: int = 0             # bucketed: lanes that carry a real node
    n_compiles: int = 0               # XLA compiles charged to this plan
    lower_time_s: float = 0.0
    compile_time_s: float = 0.0

    @property
    def n_operands(self) -> int:
        return (self.n_slice_reads + self.n_gather_reads +
                self.n_broadcast_reads + self.n_slice_writes +
                self.n_scatter_writes)

    def as_dict(self) -> dict:
        d = dict(self.__dict__)
        d["n_operands"] = self.n_operands
        return d


@dataclass
class Lowering:
    """A schedule resolved against a memory plan: the shared front half of
    both compiled paths (per-topology constants vs bucketed operands)."""

    steps: list[LoweredStep]
    aux_perm: np.ndarray
    row_of: dict[tuple[ArenaKey, int], int]
    arena_rows: dict[ArenaKey, int]
    stats: PlanStats


# -- lowering (host-side, once per topology) ---------------------------------


def _out_arena(impl: NodeImpl, fld: str) -> ArenaKey:
    return (fld, tuple(impl.out_fields[fld]))


def _input_arena(graph: Graph, impls: dict[TypeId, NodeImpl], ids,
                 slot: int, fld: str) -> ArenaKey:
    """Arena read by input slot ``(slot, fld)`` — every predecessor must
    produce ``fld`` with one shape (the mixed-shape case cannot batch)."""
    keys = set()
    for i in ids:
        pred = graph.nodes[graph.nodes[i].inputs[slot]]
        impl = impls[pred.type]
        if fld not in impl.out_fields:
            raise KeyError(
                f"batch input slot {slot} reads field {fld!r} but "
                f"predecessor type {pred.type!r} does not produce it")
        keys.add((fld, tuple(impl.out_fields[fld])))
    if len(keys) != 1:
        raise ValueError(
            f"input slot {slot} field {fld!r} mixes element shapes "
            f"{sorted(k[1] for k in keys)}; such batches cannot be lowered")
    return keys.pop()


def _warn_pq_skipped(stats: PlanStats) -> None:
    warnings.warn(
        f"PQ memory planning skipped ({stats.pq_skipped}); falling back to "
        f"first-write row order — strided reads will gather "
        f"(n_pq_planned_batches stays 0)", RuntimeWarning, stacklevel=3)


def _layout_rows(graph: Graph, sched: Schedule, impls, layout: str,
                 max_pq_vars: int, pq_chunk: bool, stats: PlanStats
                 ) -> tuple[dict, dict]:
    """Row tables ``(arena, node) -> row`` plus per-arena row counts."""
    nodes = graph.nodes
    # Declaration order = first-write (schedule) order, also the fallback
    # layout when the PQ pipeline is disabled or fails. Kept grouped per
    # step so the chunked planner can cut on step boundaries.
    var_groups: list[list[tuple[ArenaKey, int]]] = []
    for t, ids in sched:
        impl = impls[t]
        grp: list[tuple[ArenaKey, int]] = []
        for f in impl.out_fields:
            key = _out_arena(impl, f)
            grp.extend((key, i) for i in sorted(ids))
        var_groups.append(grp)
    variables = [v for grp in var_groups for v in grp]
    order = variables

    if layout == "planned":
        batches = []
        for si, (t, ids) in enumerate(sched):
            impl = impls[t]
            ids_sorted = sorted(ids)
            operands: list[tuple] = []
            for f in impl.out_fields:
                key = _out_arena(impl, f)
                operands.append(tuple((key, i) for i in ids_sorted))
            for slot, fld in impl.in_slots:
                key = _input_arena(graph, impls, ids_sorted, slot, fld)
                operands.append(tuple(
                    (key, nodes[i].inputs[slot]) for i in ids_sorted))
            batches.append(memplan.Batch(
                name=f"s{si}", result=operands[0],
                sources=tuple(operands[1:])))
        if len(variables) <= max_pq_vars:
            try:
                plan, _ = memplan.plan_rows(variables, batches)
                order = plan.order
                stats.layout = "pq"
                stats.n_pq_planned_batches = len(plan.planned)
                stats.n_pq_erased_batches = len(plan.erased)
            except Exception:   # noqa: BLE001 — planner is best-effort
                stats.pq_skipped = "joint PQ planning raised"
                _warn_pq_skipped(stats)
        elif pq_chunk:
            cp = memplan.plan_rows_chunked(var_groups, batches, max_pq_vars)
            order = cp.order
            stats.layout = "pq-chunked"
            stats.n_pq_planned_batches = cp.n_planned
            stats.n_pq_erased_batches = cp.n_erased
            stats.n_pq_chunks = cp.n_chunks
            if cp.n_skipped_chunks:
                # Partial degradation is visible in the flag; only a fully
                # unplanned layout warrants the warning.
                stats.pq_skipped = (f"{cp.n_skipped_chunks}/{cp.n_chunks} "
                                    f"chunks fell back to declaration order")
                if cp.n_skipped_chunks == cp.n_chunks:
                    _warn_pq_skipped(stats)
        else:
            stats.pq_skipped = (
                f"{len(variables)} layout vars exceed "
                f"max_pq_vars={max_pq_vars} and chunked planning is off")
            _warn_pq_skipped(stats)
    # Split the joint order into per-arena row tables: an operand that is
    # globally contiguous stays contiguous after the split because all of
    # its variables live in one arena.
    row_of: dict[tuple[ArenaKey, int], int] = {}
    counters: dict[ArenaKey, int] = {}
    for key, node_id in order:
        row = counters.get(key, 0)
        counters[key] = row + 1
        row_of[(key, node_id)] = row
    return row_of, counters


def lower_schedule(graph: Graph, sched: Schedule,
                   impls: dict[TypeId, NodeImpl], *, layout: str = "planned",
                   max_pq_vars: int = 512, pq_chunk: bool = True) -> Lowering:
    """Resolve every batch operand of ``sched`` to arena rows + access modes.
    Shared by the per-topology and bucketed compilers."""
    stats = PlanStats(n_steps=len(sched))
    row_of, arena_rows = _layout_rows(graph, sched, impls, layout,
                                      max_pq_vars, pq_chunk, stats)
    nodes = graph.nodes
    steps: list[LoweredStep] = []
    aux_perm: list[int] = []
    st = stats
    for t, ids in sched:
        impl = impls[t]
        out_fields = list(impl.out_fields)
        primary = _out_arena(impl, out_fields[0])
        # Canonical element order: ascending rows of the primary output
        # arena, so the primary write is always one contiguous slice-assign
        # whenever the planner made its rows adjacent.
        ids_c = sorted(ids, key=lambda i: row_of[(primary, i)])
        fallback = False

        outputs: list[tuple[str, LoweredOperand]] = []
        for f in out_fields:
            key = _out_arena(impl, f)
            rows = [row_of[(key, i)] for i in ids_c]
            start = memplan.operand_run(
                {v: r for v, r in zip(ids_c, rows)}, ids_c)
            if start is not None:
                outputs.append((f, LoweredOperand(key, SLICE, start)))
                st.n_slice_writes += 1
            else:
                outputs.append((f, LoweredOperand(key, SCATTER,
                                                  rows=tuple(rows))))
                st.n_scatter_writes += 1
                fallback = True

        inputs: list[LoweredOperand] = []
        for slot, fld in impl.in_slots:
            key = _input_arena(graph, impls, ids_c, slot, fld)
            srcs = [nodes[i].inputs[slot] for i in ids_c]
            rows = [row_of[(key, s)] for s in srcs]
            if len(set(srcs)) == 1:
                inputs.append(LoweredOperand(key, BROADCAST, rows[0]))
                st.n_broadcast_reads += 1
                continue
            start = memplan.operand_run(
                dict(zip(srcs, rows)), srcs) if len(set(srcs)) == len(srcs) \
                else None
            if start is not None:
                inputs.append(LoweredOperand(key, SLICE, start))
                st.n_slice_reads += 1
            else:
                inputs.append(LoweredOperand(key, GATHER,
                                             rows=tuple(rows)))
                st.n_gather_reads += 1
                fallback = True

        if fallback:
            st.n_gather_fallback_steps += 1
        steps.append(LoweredStep(
            type=t, ids=tuple(ids_c), k=len(ids_c),
            aux_start=len(aux_perm),
            inputs=tuple(inputs), outputs=tuple(outputs)))
        aux_perm.extend(ids_c)
    stats.n_arenas = len(arena_rows)
    return Lowering(steps=steps, aux_perm=np.asarray(aux_perm, np.int32),
                    row_of=row_of, arena_rows=arena_rows, stats=stats)


def _params_kind(params: Any) -> tuple:
    """AOT executables are pinned to exact input avals; both compiled
    executors key them per params pytree kind (e.g. eval with None vs
    training with a params dict) so alternating runs never retrace."""
    return (jax.tree.structure(params),
            tuple((x.shape, jnp.result_type(x).name)
                  for x in jax.tree.leaves(params)))


def _node_aux_np(graph: Graph, perm: np.ndarray) -> np.ndarray:
    """Host-side flat aux vector: node ``aux`` attrs in plan order."""
    if perm.size == 0:
        return np.zeros(0, np.int32)
    aux_all = np.asarray([n.attrs.get("aux", 0) for n in graph.nodes],
                         np.int32)
    return aux_all[perm]


def _gather_node_aux(graph: Graph, perm: np.ndarray) -> jnp.ndarray:
    """The flat per-run aux operand: node ``aux`` attrs in plan order."""
    return jnp.asarray(_node_aux_np(graph, perm))


class PlanResult:
    """Arena-backed per-node access, mirroring ``ExecResult``'s API."""

    def __init__(self, graph: Graph, impls: dict[TypeId, NodeImpl],
                 arenas: dict[ArenaKey, jnp.ndarray],
                 row_of: dict[tuple[ArenaKey, int], int]):
        self._graph = graph
        self._impls = impls
        self.arenas = arenas
        self._row_of = row_of

    def node(self, i: int) -> dict[str, jnp.ndarray]:
        impl = self._impls[self._graph.nodes[i].type]
        out = {}
        for f, shape in impl.out_fields.items():
            key = (f, tuple(shape))
            out[f] = self.arenas[key][self._row_of[(key, i)]]
        return out

    def nodes_with_field(self, fld: str):
        for n in self._graph.nodes:
            impl = self._impls.get(n.type)
            if impl and fld in impl.out_fields:
                yield n.id

    def field(self, fld: str, ids) -> jnp.ndarray:
        keys = set()
        for i in ids:
            impl = self._impls[self._graph.nodes[i].type]
            if fld not in impl.out_fields:
                raise KeyError(f"node {i} ({impl.name}) has no field {fld!r}")
            keys.add((fld, tuple(impl.out_fields[fld])))
        if len(keys) != 1:
            raise ValueError(
                f"field {fld!r} has mixed shapes "
                f"{sorted(k[1] for k in keys)} across the requested nodes")
        key = keys.pop()
        rows = np.asarray([self._row_of[(key, i)] for i in ids], np.int32)
        return self.arenas[key][rows]

    def arena_rows(self, fld: str, ids) -> tuple[jnp.ndarray, np.ndarray]:
        """(arena, row-index vector) for ``fld`` at ``ids`` — the raw
        ingredients of :meth:`field`, for callers that want to fuse the
        gather into a larger jitted program (e.g. the serve engine's
        single-dispatch commit scatter) instead of paying one eager jax
        dispatch per field."""
        keys = set()
        for i in ids:
            impl = self._impls[self._graph.nodes[i].type]
            if fld not in impl.out_fields:
                raise KeyError(f"node {i} ({impl.name}) has no field {fld!r}")
            keys.add((fld, tuple(impl.out_fields[fld])))
        if len(keys) != 1:
            raise ValueError(
                f"field {fld!r} has mixed shapes "
                f"{sorted(k[1] for k in keys)} across the requested nodes")
        key = keys.pop()
        rows = np.asarray([self._row_of[(key, i)] for i in ids], np.int32)
        return self.arenas[key], rows


class CompiledPlan:
    """A schedule + memory plan lowered to a single jitted program whose
    index vectors are trace-time constants (one executable per topology).

    ``donate=True`` donates the arena pool to XLA so outputs reuse the same
    buffers in place (no per-run allocation at all).  The trade-off: running
    the plan invalidates arrays returned by the *previous* run, so only
    enable it in throughput loops that consume each result immediately.
    """

    def __init__(self, graph: Graph, sched: Schedule,
                 impls: dict[TypeId, NodeImpl], *, layout: str = "planned",
                 max_pq_vars: int = 512, pq_chunk: bool = True,
                 donate: bool = False, gather_interpret: bool = False,
                 compile_hook: Callable[[Any], None] | None = None,
                 tracer: Tracer | None = None):
        t0 = time.perf_counter()
        self.impls = impls
        self.donate = donate
        self.gather_interpret = gather_interpret
        # Called with the cache key on every executable-cache miss, before
        # the XLA compile runs; raising aborts the build with no cache entry
        # written. The serve fault injector hangs off this.
        self.compile_hook = compile_hook
        self.tracer = tracer if tracer is not None else default_tracer()
        low = lower_schedule(graph, sched, impls, layout=layout,
                             max_pq_vars=max_pq_vars, pq_chunk=pq_chunk)
        self.steps = low.steps
        self.aux_perm = low.aux_perm
        self.row_of = low.row_of
        self.arena_rows = low.arena_rows
        self.stats = low.stats
        self.stats.lower_time_s = time.perf_counter() - t0
        # AOT executables + arena pools, keyed by the params pytree kind
        # (structure + leaf avals) so eval (None) and training (dict) runs
        # coexist without recompiling on every alternation. FIFO-capped.
        self._exes: FIFOCache = FIFOCache(4)
        self.n_dispatches = 0     # device dispatches issued by execute()

    # -- the traced program ------------------------------------------------

    def _body(self, params: Any, aux_flat: jnp.ndarray,
              arenas: dict[ArenaKey, jnp.ndarray]) -> dict[ArenaKey, jnp.ndarray]:
        from repro.kernels.gather_batch import gather_rows

        arenas = dict(arenas)
        for step in self.steps:
            impl = self.impls[step.type]
            inputs = []
            for opd in step.inputs:
                buf = arenas[opd.arena]
                if opd.mode == SLICE:
                    inputs.append(
                        jax.lax.slice_in_dim(buf, opd.start, opd.start + step.k))
                elif opd.mode == BROADCAST:
                    one = jax.lax.slice_in_dim(buf, opd.start, opd.start + 1)
                    inputs.append(
                        jnp.broadcast_to(one, (step.k,) + buf.shape[1:]))
                else:
                    inputs.append(gather_rows(
                        buf, np.asarray(opd.rows, np.int32),
                        interpret=self.gather_interpret))
            aux = jax.lax.slice_in_dim(aux_flat, step.aux_start,
                                       step.aux_start + step.k)
            out = impl.apply(params, inputs, aux)
            for f, opd in step.outputs:
                val = out[f]
                buf = arenas.get(opd.arena)
                if buf is None:
                    # First write decides the dtype; rows are never read
                    # before being written, so the fill value is dead.
                    buf = jnp.zeros(
                        (self.arena_rows[opd.arena],) + opd.arena[1], val.dtype)
                if opd.mode == SLICE:
                    buf = jax.lax.dynamic_update_slice_in_dim(
                        buf, val.astype(buf.dtype), opd.start, 0)
                else:
                    buf = buf.at[np.asarray(opd.rows, np.int32)].set(
                        val.astype(buf.dtype))
                arenas[opd.arena] = buf
        return arenas

    # -- execution ---------------------------------------------------------

    def _aux_flat(self, graph: Graph) -> jnp.ndarray:
        return _gather_node_aux(graph, self.aux_perm)

    def _ensure_executable(self, params: Any, aux_flat: jnp.ndarray) -> tuple:
        key = _params_kind(params)
        entry = self._exes.get(key)
        if entry is not None:
            return key
        if self.compile_hook is not None:
            _call_compile_hook(self.compile_hook, key,
                               {"kind": "plan", "sig": _sig_digest(key)})
        with self.tracer.span("xla.compile", cat="compile", kind="plan",
                              sig=_sig_digest(key)) as sp:
            t0 = time.perf_counter()
            shapes = jax.eval_shape(lambda p, a: self._body(p, a, {}),
                                    params, aux_flat)
            # The pool is allocated exactly once per (topology, params kind);
            # with donation XLA writes results back into these same buffers.
            pool = {k: jnp.zeros(s.shape, s.dtype) for k, s in shapes.items()}
            jitted = jax.jit(self._body,
                             donate_argnums=(2,) if self.donate else ())
            exe = jitted.lower(params, aux_flat, pool).compile()
            self._exes[key] = (exe, pool)
            self.stats.n_compiles += 1
            dt = time.perf_counter() - t0
            self.stats.compile_time_s += dt
            sp.set(lower_s=dt)
        return key

    def execute(self, graph: Graph, params: Any = None) -> PlanResult:
        """Run the plan on ``graph`` (same topology, any aux values): exactly
        one device dispatch."""
        with self.tracer.span("plan.h2d", cat="plan"):
            aux_flat = self._aux_flat(graph)
        key = self._ensure_executable(params, aux_flat)
        exe, pool = self._exes[key]
        with self.tracer.span("plan.dispatch", cat="plan"):
            arenas = exe(params, aux_flat, pool)
        self.n_dispatches += 1
        if self.donate:
            self._exes[key] = (exe, arenas)
        return PlanResult(graph, self.impls, arenas, self.row_of)


class PlanExecutor:
    """Drop-in counterpart of ``DynamicExecutor`` that runs compiled plans.

    Plans are cached per ``(topology, policy)`` exactly like the interpreted
    executor's schedules; a cache hit costs one aux re-pack and one device
    dispatch.
    """

    def __init__(self, impls: dict[TypeId, NodeImpl], params: Any, *,
                 layout: str = "planned", max_pq_vars: int = 512,
                 pq_chunk: bool = True, donate: bool = False,
                 gather_interpret: bool = False,
                 cache: FIFOCache | None = None, namespace: Any = None,
                 compile_hook: Callable[[Any], None] | None = None,
                 tracer: Tracer | None = None):
        self.impls = impls
        self.params = params
        self.layout = layout
        self.max_pq_vars = max_pq_vars
        self.pq_chunk = pq_chunk
        self.donate = donate
        self.gather_interpret = gather_interpret
        self.compile_hook = compile_hook
        self.tracer = tracer if tracer is not None else default_tracer()
        # FIFO-capped: each entry pins a policy, the lowered steps, AOT
        # executables, and arena pools — an unbounded topology stream must
        # not grow host/device memory forever. The serve layer passes one
        # shared cache (namespaced per workload family) across its engines.
        self._plans = cache if cache is not None else FIFOCache(32)
        self._ns = namespace

    def plan_for(self, graph: Graph,
                 policy: Policy | Callable[[Graph], Schedule],
                 stats: ExecStats | None = None) -> CompiledPlan:
        # "plan" tags the entry kind: a cache shared with a
        # BucketedPlanExecutor (same namespace/topology/policy) must never
        # hand this executor a BucketedPack, or vice versa.
        key = ("plan", self._ns, graph.topology_key(),
               policy_cache_key(policy))
        plan = self._plans.get(key)
        if plan is None:
            t0 = time.perf_counter()
            with self.tracer.span("plan.schedule", cat="plan"):
                sched = resolve_schedule(graph, policy)
            t1 = time.perf_counter()
            with self.tracer.span("plan.lower", cat="plan"):
                plan = CompiledPlan(graph, sched, self.impls,
                                    layout=self.layout,
                                    max_pq_vars=self.max_pq_vars,
                                    pq_chunk=self.pq_chunk,
                                    donate=self.donate,
                                    gather_interpret=self.gather_interpret,
                                    compile_hook=self.compile_hook,
                                    tracer=self.tracer)
            self._plans[key] = plan
            if stats is not None:
                stats.schedule_time += t1 - t0
                stats.lower_time += plan.stats.lower_time_s
        return plan

    def run(self, graph: Graph, policy: Policy | Callable[[Graph], Schedule],
            stats: ExecStats | None = None, params: Any = None) -> PlanResult:
        stats = stats if stats is not None else ExecStats()
        with self.tracer.span("plan.pack", cat="plan"):
            plan = self.plan_for(graph, policy, stats)
        compile_before = plan.stats.compile_time_s
        t1 = time.perf_counter()
        res = plan.execute(graph, params if params is not None else self.params)
        with self.tracer.span("plan.block", cat="plan"):
            jax.block_until_ready(list(res.arenas.values()))
        dt = time.perf_counter() - t1
        compiled_s = plan.stats.compile_time_s - compile_before
        if compiled_s > 0:
            # Fold one-time XLA compilation (first run, or a new params kind)
            # into lower_time, not exec_time, so the Fig. 8 decomposition
            # stays honest.
            stats.lower_time += compiled_s
            stats.n_compiles += 1
            dt = max(dt - compiled_s, 0.0)
        stats.exec_time += dt
        stats.n_batches += plan.stats.n_steps
        stats.n_launches += 1
        return res


# ---------------------------------------------------------------------------
# Bucketed plan families (deviation #4)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class BucketStepSpec:
    """The trace-time shape of one padded step: its type (selects the impl),
    padded width, and the arenas it touches. Index vectors are *not* here —
    they are runtime operands, which is the whole point."""

    type: TypeId
    width: int
    in_arenas: tuple[ArenaKey, ...]
    out_arenas: tuple[tuple[str, ArenaKey], ...]


@dataclass(frozen=True)
class BucketSpec:
    """The bucket signature: everything the jitted program specializes on.
    Two topologies with equal specs share one XLA executable.

    ``n_shards`` is 1 for the single-device program; the sharded executor
    re-keys the same signature at its replica count (the per-shard program
    is identical — only the leading device axis of the operands changes),
    so the LRU executable cache and persistent-jaxcache keys distinguish
    replicated from single-device builds without any new cache machinery.
    """

    steps: tuple[BucketStepSpec, ...]
    arena_rows: tuple[tuple[ArenaKey, int], ...]   # padded rows, sorted
    n_shards: int = 1

    @property
    def n_index_lanes(self) -> int:
        return sum(s.width * (len(s.in_arenas) + len(s.out_arenas))
                   for s in self.steps)

    @property
    def n_aux_lanes(self) -> int:
        return sum(s.width for s in self.steps)


@dataclass(frozen=True)
class CommitSpec:
    """An optional last stage of the bucket program, for graphs made of
    recurrent fragments ``slot_type -> ... -> state node -> output node``
    whose state lives in pools threaded through ``params["slots"]`` (one
    ``(n_slots, ...)`` array per state field, read by the ``slot_type``
    impl).

    For every fragment (in the order of its ``slot_type`` node's id) the
    stage passes ``fn`` the output arena and the fragment's output row, the
    slot id (the ``slot_type`` node's ``aux``), the state arenas and rows
    (``state_fields``, written by the node whose output the output node
    reads) and the pools, and the program returns what ``fn`` returns:
    ``(tokens, new_pools)``. A slot id past the pool writes nothing if
    ``fn`` scatters with ``mode="drop"``. ``fn`` is part of the executable
    key, so pass a module-level function."""

    out_field: str
    state_fields: tuple[str, ...]
    slot_type: TypeId
    fn: Callable


@dataclass(frozen=True)
class CommitLayout:
    """Where one pack's commit stage reads: the output arena, the state
    arenas, and the number of fragments. Fixed by the pack; part of the
    executable key (the commit index vector's length is an operand
    shape)."""

    y_arena: ArenaKey
    state_arenas: tuple[ArenaKey, ...]
    n: int


class BucketedPack:
    """One topology packed against its bucket: the runtime index operands
    plus the row table for result access. Cheap to build — no XLA.

    ``impls`` pins the impl dict for as long as the pack lives in a shared
    cache: cache keys namespace on ``id(impls)``, and an unpinned dict's id
    could be recycled onto a different workload's impls after GC.

    A pack built for a committing executor also carries its
    :class:`CommitLayout` and ``commit_idx``: the fragments' slot lanes in
    the aux vector, output rows, then state rows per field, each ``n``
    long, device-resident like ``idxpack``."""

    def __init__(self, spec: BucketSpec, idxpack: jnp.ndarray,
                 aux_perm: np.ndarray, row_of: dict, stats: PlanStats,
                 impls: dict[TypeId, NodeImpl] | None = None,
                 idxpack_np: np.ndarray | None = None):
        self.spec = spec
        self.idxpack = idxpack        # (n_index_lanes,) int32, device-resident
        # Host copy kept for the sharded executor, which stacks K shards'
        # index vectors on a leading device axis each round.
        self.idxpack_np = (idxpack_np if idxpack_np is not None
                           else np.asarray(idxpack))
        self.aux_perm = aux_perm      # (n_aux_lanes,) int32 node ids
        self.row_of = row_of
        self.stats = stats
        self.impls = impls
        self.commit_layout: CommitLayout | None = None
        self.commit_idx: jnp.ndarray | None = None


def _commit_pack(graph: Graph, pack: BucketedPack,
                 impls: dict[TypeId, NodeImpl],
                 commit: CommitSpec) -> tuple[CommitLayout, jnp.ndarray]:
    """The commit stage's row vectors for ``pack``: one fragment per
    ``slot_type`` node, traced forward to the node that consumes it and
    writes every state field, then to that node's consumer that writes
    ``out_field``."""
    nodes = graph.nodes
    consumers: dict[int, list[int]] = {}
    for n in nodes:
        for p in n.inputs:
            consumers.setdefault(p, []).append(n.id)

    def consumer_with(i: int, fields) -> int:
        for c in consumers.get(i, ()):
            if all(f in impls[nodes[c].type].out_fields for f in fields):
                return c
        raise ValueError(
            f"node {i} ({nodes[i].type}) has no consumer writing "
            f"{list(fields)}: the graph does not fit the commit stage")

    lane_of: dict[int, int] = {}
    for lane, i in enumerate(pack.aux_perm.tolist()):
        lane_of.setdefault(i, lane)   # pad lanes repeat the last real id
    slot_lanes, y_rows = [], []
    state_rows: list[list[int]] = [[] for _ in commit.state_fields]
    y_key = s_keys = None
    for n in nodes:
        if n.type != commit.slot_type:
            continue
        st = consumer_with(n.id, commit.state_fields)
        out = consumer_with(st, (commit.out_field,))
        y_key = (commit.out_field, tuple(
            impls[nodes[out].type].out_fields[commit.out_field]))
        s_keys = tuple((f, tuple(impls[nodes[st].type].out_fields[f]))
                       for f in commit.state_fields)
        slot_lanes.append(lane_of[n.id])
        y_rows.append(pack.row_of[(y_key, out)])
        for rows, k in zip(state_rows, s_keys):
            rows.append(pack.row_of[(k, st)])
    if y_key is None:
        raise ValueError(f"no {commit.slot_type!r} node to commit")
    vec = np.asarray(slot_lanes + y_rows + sum(state_rows, []), np.int32)
    return CommitLayout(y_key, s_keys, len(slot_lanes)), jnp.asarray(vec)


def _read_rows(opd: LoweredOperand, k: int) -> list[int]:
    if opd.mode == GATHER:
        return list(opd.rows)
    if opd.mode == BROADCAST:
        return [opd.start] * k
    return list(range(opd.start, opd.start + k))


def pack_bucketed(low: Lowering, *, ladder: tuple[int, ...] | None = None,
                  pad_steps: bool = True,
                  impls: dict[TypeId, NodeImpl] | None = None) -> BucketedPack:
    """Pad a lowering up to bucket boundaries and pack its index operands.

    - every operand (slice, broadcast, or gather alike) becomes a runtime
      index vector of the step's padded width — uniform access maximizes
      spec sharing across topologies;
    - pad *lanes* replicate the last real lane on reads and target the
      arena's reserved trash row (the last padded row, never a real row) on
      writes;
    - every step of a run of consecutive same-type steps takes one width,
      the bucket of the run's widest step, so the spec depends only on the
      run's length and its widest step, not on how the width falls off
      along the run (a wave of fresh trees has a new depth profile every
      time); a run of one step pads as it always did;
    - pad *steps* (run-length padding of consecutive same-type steps)
      re-execute the run's last real step with all-trash writes, so a chain
      of 11 cells and a chain of 13 share the 16-step program.
    """
    # Rows pad to the bucket rung plus one reserved trash row *outside* the
    # rung, so an arena sitting exactly on a boundary (the common case for
    # bucketed widths) does not spill the whole spec into the next bucket.
    rows_p = {k: bucket_up(r, ladder) + 1 for k, r in low.arena_rows.items()}
    spec_steps: list[BucketStepSpec] = []
    idx_parts: list[np.ndarray] = []
    aux_perm: list[int] = []
    n_pad = 0

    def emit(step: LoweredStep, wp: int, pad: bool) -> None:
        in_keys = []
        in_idx = []
        for opd in step.inputs:
            rows = _read_rows(opd, step.k)
            rows += [rows[-1]] * (wp - step.k)
            in_idx.append(np.asarray(rows, np.int32))
            in_keys.append(opd.arena)
        out_keys = []
        out_idx = []
        for f, opd in step.outputs:
            trash = rows_p[opd.arena] - 1
            if pad:
                rows = [trash] * wp
            else:
                rows = (list(opd.rows) if opd.mode == SCATTER
                        else list(range(opd.start, opd.start + step.k)))
                rows += [trash] * (wp - step.k)
            out_idx.append(np.asarray(rows, np.int32))
            out_keys.append((f, opd.arena))
        idx_parts.extend(in_idx + out_idx)
        ids = list(step.ids) + [step.ids[-1]] * (wp - step.k)
        aux_perm.extend(ids)
        spec_steps.append(BucketStepSpec(
            type=step.type, width=wp, in_arenas=tuple(in_keys),
            out_arenas=tuple(out_keys)))

    # Group maximal runs of consecutive same-type steps; pad run lengths.
    i = 0
    while i < len(low.steps):
        j = i
        while j < len(low.steps) and low.steps[j].type == low.steps[i].type:
            j += 1
        run = low.steps[i:j]
        wp = bucket_up(max(s.k for s in run), ladder)
        for s in run:
            emit(s, wp, pad=False)
        if pad_steps:
            # Run lengths pad on the pure power-of-two ladder: a width
            # ladder's floor exists to merge small *batches*, and applying
            # it here would multiply every short run into `floor` steps.
            for _ in range(bucket_up(len(run)) - len(run)):
                emit(run[-1], wp, pad=True)
                n_pad += 1
        i = j

    spec = BucketSpec(tuple(spec_steps),
                      tuple(sorted(rows_p.items(), key=repr)))
    stats = low.stats
    stats.bucketed = True
    stats.n_pad_steps = n_pad
    stats.n_real_lanes = sum(s.k for s in low.steps)
    idxpack = (np.concatenate(idx_parts) if idx_parts
               else np.zeros(0, np.int32))
    return BucketedPack(spec, jnp.asarray(idxpack),
                        np.asarray(aux_perm, np.int32), low.row_of, stats,
                        impls=impls, idxpack_np=idxpack)


class _BucketProgram:
    """The traced shape-polymorphic program for one bucket signature: step
    structure and widths are constants, every index vector is an operand.
    ``commit``/``layout`` give :meth:`body_and_commit` its last stage."""

    def __init__(self, spec: BucketSpec, impls: dict[TypeId, NodeImpl], *,
                 gather_interpret: bool = False, fused: Any = "auto",
                 fused_interpret: bool = False,
                 commit: CommitSpec | None = None,
                 layout: CommitLayout | None = None):
        self.spec = spec
        self.impls = impls
        self.commit = commit
        self.layout = layout
        self.gather_interpret = gather_interpret
        self.fused = fused
        self.fused_interpret = fused_interpret
        self.rows_p = dict(spec.arena_rows)

    def _fused_fn(self, impl: NodeImpl):
        fn = getattr(impl, "fused_gather", None)
        if fn is None or self.fused is False:
            return None
        if self.fused == "auto" and jax.default_backend() != "tpu":
            return None
        return fn

    def body(self, params: Any, idxpack: jnp.ndarray, aux_pack: jnp.ndarray,
             arenas: dict[ArenaKey, jnp.ndarray]) -> dict[ArenaKey, jnp.ndarray]:
        from repro.kernels.gather_batch import gather_rows

        arenas = dict(arenas)
        off = aoff = 0
        for bs in self.spec.steps:
            impl = self.impls[bs.type]
            w = bs.width
            idxs = []
            for _ in bs.in_arenas:
                idxs.append(jax.lax.slice_in_dim(idxpack, off, off + w))
                off += w
            aux = jax.lax.slice_in_dim(aux_pack, aoff, aoff + w)
            aoff += w
            fused = self._fused_fn(impl)
            if fused is not None:
                out = fused(params, [arenas[k] for k in bs.in_arenas], idxs,
                            aux, interpret=self.fused_interpret or None)
            else:
                inputs = [gather_rows(arenas[k], ix,
                                      interpret=self.gather_interpret)
                          for k, ix in zip(bs.in_arenas, idxs)]
                out = impl.apply(params, inputs, aux)
            for f, key in bs.out_arenas:
                oidx = jax.lax.slice_in_dim(idxpack, off, off + w)
                off += w
                val = out[f]
                buf = arenas.get(key)
                if buf is None:
                    # First write decides the dtype; real rows are written
                    # before any read, pad lanes only ever hit the trash row.
                    buf = jnp.zeros((self.rows_p[key],) + key[1], val.dtype)
                arenas[key] = buf.at[oidx].set(val.astype(buf.dtype))
        return arenas

    def body_and_commit(self, params: Any, idxpack: jnp.ndarray,
                        aux_pack: jnp.ndarray,
                        arenas: dict[ArenaKey, jnp.ndarray],
                        commit_idx: jnp.ndarray) -> tuple:
        """``body`` followed by the commit stage; returns ``(tokens,
        new_pools, arenas)`` with ``new_pools`` keyed by state field."""
        commit, layout = self.commit, self.layout
        arenas = self.body(params, idxpack, aux_pack, arenas)
        n = layout.n
        parts = [jax.lax.slice_in_dim(commit_idx, i * n, (i + 1) * n)
                 for i in range(2 + len(layout.state_arenas))]
        pools = params["slots"]
        toks, new_pools = commit.fn(
            arenas[layout.y_arena], parts[1], aux_pack[parts[0]],
            [arenas[k] for k in layout.state_arenas], parts[2:],
            [pools[f] for f in commit.state_fields])
        return toks, dict(zip(commit.state_fields, new_pools)), arenas


class BucketedPlanExecutor:
    """Shape-polymorphic counterpart of :class:`PlanExecutor`.

    Per-topology work is host-side only: resolve the schedule, lower it,
    pack index vectors (all cached FIFO by topology fingerprint). The XLA
    executable is cached by *bucket signature* — typically a handful of
    entries serve an unbounded topology stream, so compile cost amortizes
    across every topology in the bucket instead of recurring per topology.

    With ``commit`` (a :class:`CommitSpec`) every program ends in the
    commit stage: :meth:`dispatch_packed`'s handle then also yields the
    tokens and new state pools (:meth:`InFlightDispatch.tokens`).
    """

    def __init__(self, impls: dict[TypeId, NodeImpl], params: Any, *,
                 layout: str = "planned", max_pq_vars: int = 512,
                 pq_chunk: bool = True, donate: bool = False,
                 gather_interpret: bool = False, fused: Any = "auto",
                 fused_interpret: bool = False,
                 ladder: tuple[int, ...] | None = None,
                 pad_steps: bool = True,
                 pack_cache: FIFOCache | None = None,
                 exe_cache: FIFOCache | None = None, namespace: Any = None,
                 compile_hook: Callable[[Any], None] | None = None,
                 tracer: Tracer | None = None,
                 commit: CommitSpec | None = None):
        self.impls = impls
        self.params = params
        self.commit = commit
        self.layout = layout
        self.max_pq_vars = max_pq_vars
        self.pq_chunk = pq_chunk
        self.donate = donate
        self.gather_interpret = gather_interpret
        self.fused = fused
        self.fused_interpret = fused_interpret
        self.ladder = tuple(ladder) if ladder else None
        self.pad_steps = pad_steps
        # Consulted with the executable-cache key on every miss, before the
        # XLA build; raising aborts the compile with the cache untouched —
        # the serve degradation ladder's compile-failure injection point.
        self.compile_hook = compile_hook
        self.tracer = tracer if tracer is not None else default_tracer()
        # Packs are cheap (host-side numpy); executables are the expensive
        # entries and are LRU-kept so hot buckets survive topology churn.
        self._packs = pack_cache if pack_cache is not None else FIFOCache(256)
        self._exes = exe_cache if exe_cache is not None else LRUCache(32)
        self._ns = namespace
        self.n_bucket_compiles = 0
        self.compile_time_s = 0.0

    def _pack_key(self, graph: Graph,
                  policy: Policy | Callable[[Graph], Schedule],
                  ladder: tuple[int, ...] | None, layout: str) -> tuple:
        # The effective ladder is part of the key: the async serve path
        # packs the same topology at coarser ladders to bridge onto an
        # already-compiled bucket while the native one is still building.
        # A committing pack carries its commit vectors, and a pack laid
        # out other than the executor's own layout carries that layout, so
        # each is kept apart from a plain pack of the same topology.
        key = ("pack", self._ns, graph.topology_key(),
               policy_cache_key(policy), ladder)
        if layout != self.layout:
            key += (layout,)
        return key if self.commit is None else key + (self.commit,)

    def pack_for(self, graph: Graph,
                 policy: Policy | Callable[[Graph], Schedule],
                 stats: ExecStats | None = None,
                 ladder: tuple[int, ...] | None = None,
                 layout: str | None = None) -> BucketedPack:
        """The bucketed pack of ``graph`` under ``policy``, cached.

        ``layout`` overrides the executor's row layout for this pack. The
        bucket spec does not depend on it: the program reads every operand
        through its index vector, so only the index values differ. A pack
        in declaration order (``"schedule"``) lowers in time linear in the
        graph, where the PQ-tree plan can take minutes for a few hundred
        layout variables, joint or chunked."""
        lad = self.ladder if ladder is None else tuple(ladder)
        lay = self.layout if layout is None else layout
        key = self._pack_key(graph, policy, lad, lay)
        pack = self._packs.get(key)
        if pack is None:
            t0 = time.perf_counter()
            with self.tracer.span("plan.schedule", cat="plan"):
                sched = resolve_schedule(graph, policy)
            t1 = time.perf_counter()
            with self.tracer.span("plan.lower", cat="plan"):
                low = lower_schedule(graph, sched, self.impls,
                                     layout=lay,
                                     max_pq_vars=self.max_pq_vars,
                                     pq_chunk=self.pq_chunk)
                pack = pack_bucketed(low, ladder=lad,
                                     pad_steps=self.pad_steps,
                                     impls=self.impls)
                if self.commit is not None:
                    pack.commit_layout, pack.commit_idx = _commit_pack(
                        graph, pack, self.impls, self.commit)
            pack.stats.lower_time_s = time.perf_counter() - t1
            self._packs[key] = pack
            if stats is not None:
                stats.schedule_time += t1 - t0
                stats.lower_time += pack.stats.lower_time_s
        return pack

    def pack_ready(self, graph: Graph,
                   policy: Policy | Callable[[Graph], Schedule],
                   ladder: tuple[int, ...] | None = None,
                   layout: str | None = None) -> BucketedPack | None:
        """Cached pack for ``(graph, policy, ladder, layout)`` or ``None`` —
        a pure probe: no lowering, no hit/miss accounting. The async serve
        loop uses this each round so host-side lowering stays off the loop."""
        lad = self.ladder if ladder is None else tuple(ladder)
        lay = self.layout if layout is None else layout
        return self._packs.peek(self._pack_key(graph, policy, lad, lay))

    def executable_key(self, pack: BucketedPack, params: Any) -> tuple:
        key = (self._ns, pack.spec, _params_kind(params))
        if self.commit is None:
            return key
        return key + (self.commit, pack.commit_layout)

    def executable_ready(self, pack: BucketedPack, params: Any) -> bool:
        """True when the bucket executable is already in the shared cache —
        a pure probe (no build, no LRU refresh, no counter bump)."""
        return self._exes.peek(self.executable_key(pack, params)) is not None

    def _ensure_executable(self, pack: BucketedPack, params: Any
                           ) -> tuple[Any, tuple, float]:
        """Returns ``(key, entry, compile_s)``. The entry comes straight
        from the locked cache ``get`` (or the fresh build) — callers must
        not re-read the shared cache afterwards: a concurrent insert could
        evict the key between the check and the act."""
        return self.build_executable(pack, params)

    def build_executable(self, pack: BucketedPack, params: Any,
                         span_args: dict | None = None,
                         abort_check: Callable[[], bool] | None = None
                         ) -> tuple[Any, tuple, float]:
        """Build (or fetch) the bucket executable for ``pack``; safe to call
        from a background compile worker — caches are locked and the tracer
        keeps per-thread span stacks. ``span_args`` (e.g. ``bg=True``,
        ``queue_wait_s``) are stamped onto the ``xla.compile`` span so the
        Fig. 8 decomposition can attribute off-loop compile time.
        ``abort_check`` is consulted after the compile hook and before the
        XLA build: a worker whose job was timed out and abandoned while it
        sat in the hook bails here instead of burning a wasted compile (an
        abort raises, so nothing is cached)."""
        key = self.executable_key(pack, params)
        entry = self._exes.get(key)
        if entry is not None:
            return key, entry, 0.0
        ctx = {"kind": "bucketed", "sig": _sig_digest(pack.spec)}
        ctx.update(span_args or {})
        if abort_check is not None:
            # Hook-only (never stamped on spans): lets an injected hang
            # (FaultInjector.on_compile) sleep interruptibly and release
            # the abandoned worker thread promptly.
            ctx["abort"] = abort_check
        if self.compile_hook is not None:
            _call_compile_hook(self.compile_hook, key, ctx)
        if abort_check is not None and abort_check():
            raise RuntimeError(
                f"compile of bucket {_sig_digest(pack.spec)} aborted "
                f"(job abandoned before the XLA build)")
        with self.tracer.span("xla.compile", cat="compile", kind="bucketed",
                              bucket=_sig_digest(pack.spec),
                              steps=len(pack.spec.steps),
                              shards=pack.spec.n_shards,
                              **(span_args or {})) as sp:
            t0 = time.perf_counter()
            prog = _BucketProgram(pack.spec, self.impls,
                                  gather_interpret=self.gather_interpret,
                                  fused=self.fused,
                                  fused_interpret=self.fused_interpret,
                                  commit=self.commit,
                                  layout=pack.commit_layout)
            idx_spec = jax.ShapeDtypeStruct((pack.spec.n_index_lanes,),
                                            jnp.int32)
            aux_spec = jax.ShapeDtypeStruct((pack.spec.n_aux_lanes,),
                                            jnp.int32)
            shapes = jax.eval_shape(
                lambda p, ix, ax: prog.body(p, ix, ax, {}),
                params, idx_spec, aux_spec)
            pool = {k: jnp.zeros(s.shape, s.dtype) for k, s in shapes.items()}
            donate = (3,) if self.donate else ()
            if self.commit is None:
                exe = jax.jit(prog.body, donate_argnums=donate).lower(
                    params, idx_spec, aux_spec, pool).compile()
            else:
                exe = jax.jit(prog.body_and_commit,
                              donate_argnums=donate).lower(
                    params, idx_spec, aux_spec, pool,
                    pack.commit_idx).compile()
            # The impls dict rides along to pin its id for the entry's
            # lifetime (the AOT executable itself holds no reference to it):
            # shared caches namespace on id(impls), which must not be
            # recycled.
            entry = (exe, pool, self.impls)
            self._exes[key] = entry
            dt = time.perf_counter() - t0
            sp.set(lower_s=dt)
        self.n_bucket_compiles += 1
        self.compile_time_s += dt
        pack.stats.n_compiles += 1
        pack.stats.compile_time_s += dt
        return key, entry, dt

    def run(self, graph: Graph, policy: Policy | Callable[[Graph], Schedule],
            stats: ExecStats | None = None, params: Any = None) -> PlanResult:
        stats = stats if stats is not None else ExecStats()
        with self.tracer.span("plan.pack", cat="plan"):
            pack = self.pack_for(graph, policy, stats)
        return self.run_packed(graph, pack, stats, params=params)

    def run_packed(self, graph: Graph, pack: BucketedPack,
                   stats: ExecStats | None = None,
                   params: Any = None) -> PlanResult:
        """Execute ``graph`` through an explicit pack — the pack need not be
        the graph's native one, only index/aux-compatible (the coarse-bucket
        tier runs a small round through a wider pack of the same topology)."""
        return self.dispatch_packed(graph, pack, stats, params=params).block()

    def dispatch_packed(self, graph: Graph, pack: BucketedPack,
                        stats: ExecStats | None = None,
                        params: Any = None) -> "InFlightDispatch":
        """Launch ``graph`` through ``pack`` without synchronizing: the
        bucket program is handed to the device (jax dispatch is async) and
        an :class:`InFlightDispatch` handle comes back immediately. The
        caller overlaps host work — the serve engine packs round t+1 here —
        and calls ``handle.block()`` when it actually needs the arenas, or
        ``handle.tokens()`` for a committing executor's tokens and pools.

        Donation rotation and stat accounting are deferred to the first of
        those: until the caller commits, the cached executable entry still
        owns the pre-dispatch pool, so a failed/abandoned round leaves the
        cache coherent."""
        stats = stats if stats is not None else ExecStats()
        tr = self.tracer
        params = params if params is not None else self.params
        with tr.span("plan.h2d", cat="plan"):
            # Host gather only: the AOT executable accepts the np vector
            # and folds the transfer into the dispatch call, instead of
            # paying a separate eager device-put dispatch per round.
            aux = _node_aux_np(graph, pack.aux_perm)
        with tr.span("plan.dispatch", cat="plan"):
            # The executable's lookup (a build, on the tiers that compile
            # on the loop, nests its own xla.compile span), then the call
            # that hands the program to the device.
            key, entry, compile_s = self._ensure_executable(pack, params)
            exe, pool, impls_pin = entry
            t1 = time.perf_counter()
            if self.commit is None:
                out = (None, None, exe(params, pack.idxpack, aux, pool))
            else:
                out = exe(params, pack.idxpack, aux, pool, pack.commit_idx)
            dispatch_s = time.perf_counter() - t1
        return InFlightDispatch(self, graph, pack, key, exe, out,
                                impls_pin, stats, dispatch_s, compile_s)


class InFlightDispatch:
    """Handle to a dispatched-but-unsynchronized bucket program run.

    ``block()`` waits for the device, rotates the donation pool, books the
    exec stats (dispatch-call time + block-wait time — the overlap gap in
    between is *not* charged, so ``exec_s`` stays honest under pipelining)
    and returns the :class:`PlanResult`. Idempotent: repeated calls return
    the same result. A committing program's run is read with ``tokens()``
    instead, which waits for the tokens alone; ``in_program`` tells the
    two apart."""

    def __init__(self, executor: BucketedPlanExecutor, graph: Graph,
                 pack: BucketedPack, key: tuple, exe: Any, out: tuple,
                 impls_pin: Any, stats: ExecStats, dispatch_s: float,
                 compile_s: float):
        self._ex = executor
        self._graph = graph
        self._pack = pack
        self._key = key
        self._exe = exe
        self._toks, self._pools, self._arenas = out
        self._impls_pin = impls_pin
        self._stats = stats
        self._dispatch_s = dispatch_s
        self._compile_s = compile_s
        self._result: PlanResult | None = None
        self._settled = False

    @property
    def pending(self) -> bool:
        return not self._settled

    @property
    def in_program(self) -> bool:
        """True when the program ran the executor's commit stage."""
        return self._toks is not None

    def tokens(self) -> tuple[np.ndarray, dict]:
        """Wait for the commit stage's tokens and read them to the host:
        ``(tokens for every fragment, new state pools by field)``. The
        pools stay on the device."""
        t0 = time.perf_counter()
        with self._ex.tracer.span("plan.block", cat="plan"):
            toks = np.asarray(self._toks)
        self._settle(time.perf_counter() - t0)
        return toks, self._pools

    def block(self) -> PlanResult:
        if self._result is not None:
            return self._result
        ex = self._ex
        t0 = time.perf_counter()
        with ex.tracer.span("plan.block", cat="plan"):
            jax.block_until_ready(list(self._arenas.values()))
        self._settle(time.perf_counter() - t0)
        self._result = PlanResult(self._graph, ex.impls, self._arenas,
                                  self._pack.row_of)
        return self._result

    def _settle(self, wait_s: float) -> None:
        """Once per run: rotate the donation pool and book the stats."""
        if self._settled:
            return
        self._settled = True
        ex = self._ex
        if ex.donate:
            ex._exes[self._key] = (self._exe, self._arenas, self._impls_pin)
        st = self._stats
        if self._compile_s > 0:
            # Compilation ran before the timed dispatch; charge it to
            # lower_time so the Fig. 8 decomposition stays honest.
            st.lower_time += self._compile_s
            st.n_compiles += 1
        st.exec_time += self._dispatch_s + wait_s
        st.n_batches += self._pack.stats.n_steps
        st.n_launches += 1


# ---------------------------------------------------------------------------
# Sharded bucketed execution (data-parallel replicas)
# ---------------------------------------------------------------------------


def _merge_params(replicated: Any, per_shard: Any) -> Any:
    """Combine the replicated params pytree with a shard's slice of the
    sharded params. Dicts merge key-wise (sharded keys win); otherwise
    exactly one side may be non-None."""
    if per_shard is None:
        return replicated
    if replicated is None:
        return per_shard
    if isinstance(replicated, dict) and isinstance(per_shard, dict):
        merged = dict(replicated)
        merged.update(per_shard)
        return merged
    raise TypeError(
        "params and shard_params can only be combined when both are dicts; "
        f"got {type(replicated).__name__} and {type(per_shard).__name__}")


class ShardedBucketedPlanExecutor(BucketedPlanExecutor):
    """Data-parallel counterpart of :class:`BucketedPlanExecutor`: K shards'
    runtime operands (index packs, aux vectors, arena pools, per-shard
    params such as lm slot pools) are stacked on a leading device axis and
    the *same* bucket program runs under ``shard_map`` over a 1-D
    ``("data",)`` mesh — one executable, one dispatch, K replicas.

    The per-shard computation is the single-device program verbatim, so
    shard results are numerically identical to running each shard's graph
    through :class:`BucketedPlanExecutor` alone (pinned by
    ``tests/test_sharded.py``). Executables are cached by the bucket
    signature re-keyed at ``n_shards=K`` — the same LRU cache and
    persistent-jaxcache machinery as the single-device path.

    ``run_sharded`` requires every shard's pack to share one bucket
    signature (the serve scheduler pads shards to a common signature for
    lm rounds). When signatures diverge — e.g. a round of structurally
    different tree graphs — or some shards are idle, it degrades to
    per-shard sequential execution through the inherited single-device
    path (still bucketed, still cached; counted in
    ``n_fallback_rounds``).
    """

    def __init__(self, impls: dict[TypeId, NodeImpl], params: Any, *,
                 mesh: Any = None, n_shards: int | None = None, **kwargs):
        super().__init__(impls, params, **kwargs)
        if mesh is None:
            from repro.launch.mesh import make_data_mesh
            mesh = make_data_mesh(n_shards)
        if len(mesh.axis_names) != 1:
            raise ValueError(
                f"sharded plan execution needs a 1-D data mesh, got axes "
                f"{mesh.axis_names}")
        self.mesh = mesh
        self.axis = mesh.axis_names[0]
        self.n_shards = int(mesh.devices.size)
        if n_shards is not None and n_shards != self.n_shards:
            raise ValueError(f"mesh has {self.n_shards} devices, "
                             f"n_shards={n_shards}")
        self.n_sharded_dispatches = 0
        self.n_fallback_rounds = 0

    # -- sharded executable ---------------------------------------------------

    def shard_sharding(self) -> NamedSharding:
        """Placement of every shard-stacked operand: split on the data
        axis. The serve engine places the slot pool with this up front so
        the per-dispatch normalization below is a no-op."""
        return NamedSharding(self.mesh, PartitionSpec(self.axis))

    def sharded_executable_key(self, sspec: BucketSpec, params: Any,
                               shard_params: Any) -> tuple:
        return (self._ns, sspec, _params_kind(params),
                _params_kind(shard_params))

    def sharded_executable_ready(self, sspec: BucketSpec, params: Any,
                                 shard_params: Any) -> bool:
        """True when the shard_map executable is already cached — a pure
        probe (no build, no LRU refresh), the sharded twin of
        :meth:`BucketedPlanExecutor.executable_ready`."""
        key = self.sharded_executable_key(sspec, params, shard_params)
        return self._exes.peek(key) is not None

    def _ensure_sharded_executable(self, sspec: BucketSpec, params: Any,
                                   shard_params: Any
                                   ) -> tuple[Any, tuple, float]:
        return self.build_sharded_executable(sspec, params, shard_params)

    def build_sharded_executable(self, sspec: BucketSpec, params: Any,
                                 shard_params: Any,
                                 span_args: dict | None = None,
                                 abort_check: Callable[[], bool] | None = None
                                 ) -> tuple[Any, tuple, float]:
        """Build (or fetch) the shard_map executable for ``sspec``; returns
        ``(key, entry, compile_s)`` — see
        :meth:`BucketedPlanExecutor._ensure_executable` for why the entry
        is returned instead of re-read from the shared cache. Like
        :meth:`BucketedPlanExecutor.build_executable` this is safe from a
        background compile worker: caches are locked, ``span_args`` stamp
        the ``xla.compile`` span, and ``abort_check`` lets an abandoned
        job bail before burning the (expensive) shard_map build."""
        key = self.sharded_executable_key(sspec, params, shard_params)
        entry = self._exes.get(key)
        if entry is not None:
            return key, entry, 0.0
        ctx = {"kind": "sharded", "sig": _sig_digest(sspec)}
        ctx.update(span_args or {})
        if abort_check is not None:
            ctx["abort"] = abort_check
        if self.compile_hook is not None:
            _call_compile_hook(self.compile_hook, key, ctx)
        if abort_check is not None and abort_check():
            raise RuntimeError(
                f"compile of sharded bucket {_sig_digest(sspec)} aborted "
                f"(job abandoned before the XLA build)")
        with self.tracer.span("xla.compile", cat="compile", kind="sharded",
                              bucket=_sig_digest(sspec),
                              steps=len(sspec.steps),
                              shards=sspec.n_shards,
                              **(span_args or {})) as tsp:
            t0 = time.perf_counter()
            prog = _BucketProgram(sspec, self.impls,
                                  gather_interpret=self.gather_interpret,
                                  fused=self.fused,
                                  fused_interpret=self.fused_interpret)
            P, axis = PartitionSpec, self.axis

            def one_shard(rep, shp, idx, aux, pools):
                # shard_map hands each device a leading-axis block of size 1;
                # inside, the body is the single-device program verbatim.
                def sq(t):
                    return jax.tree.map(lambda x: jnp.squeeze(x, 0), t)

                p = _merge_params(rep, None if shp is None else sq(shp))
                out = prog.body(p, idx[0], aux[0], sq(pools))
                return jax.tree.map(lambda x: x[None], out)

            # check_vma off: the per-shard body has no collectives, so
            # there is nothing for the varying-axes check to verify, and
            # the Pallas calls inside would otherwise have to declare the
            # vma of every output shape.
            fn = jax.shard_map(one_shard, mesh=self.mesh,
                               in_specs=(P(), P(axis), P(axis), P(axis),
                                         P(axis)),
                               out_specs=P(axis), check_vma=False)
            K = self.n_shards
            idx_spec = jax.ShapeDtypeStruct((K, sspec.n_index_lanes),
                                            jnp.int32)
            aux_spec = jax.ShapeDtypeStruct((K, sspec.n_aux_lanes), jnp.int32)
            shapes = jax.eval_shape(
                lambda p, sp, ix, ax: fn(p, sp, ix, ax, {}),
                params, shard_params, idx_spec, aux_spec)
            sharding = self.shard_sharding()
            pool = {k: jax.device_put(jnp.zeros(s.shape, s.dtype), sharding)
                    for k, s in shapes.items()}
            jitted = jax.jit(fn, donate_argnums=(4,) if self.donate else ())
            exe = jitted.lower(params, shard_params, idx_spec, aux_spec,
                               pool).compile()
            entry = (exe, pool, self.impls)
            self._exes[key] = entry
            dt = time.perf_counter() - t0
            tsp.set(lower_s=dt)
        self.n_bucket_compiles += 1
        self.compile_time_s += dt
        return key, entry, dt

    # -- execution ------------------------------------------------------------

    def _run_fallback(self, graphs, policy, stats: ExecStats, params: Any,
                      shard_params: Any) -> list[PlanResult | None]:
        self.n_fallback_rounds += 1
        results: list[PlanResult | None] = []
        for s, g in enumerate(graphs):
            if g is None:
                results.append(None)
                continue
            # Host copies: a slice of a mesh-sharded leaf is replicated
            # over the mesh, and the single-device program given one would
            # be partitioned across it (Mosaic kernels refuse that).
            mine = (None if shard_params is None
                    else jax.tree.map(lambda x: np.asarray(x[s]),
                                      shard_params))
            results.append(super().run(g, policy, stats,
                                       params=_merge_params(params, mine)))
        return results

    def run_sharded(self, graphs, policy: Policy | Callable[[Graph], Schedule],
                    stats: ExecStats | None = None, params: Any = None,
                    shard_params: Any = None) -> list[PlanResult | None]:
        """Run one graph per shard (``None`` = idle shard) in one dispatch.

        ``params`` is replicated across shards; ``shard_params`` is a pytree
        whose leaves carry a leading ``n_shards`` axis (e.g. the serve
        engine's stacked lm slot pool) and is split along the mesh. Returns
        one :class:`PlanResult` per shard, viewing that shard's slice of
        the stacked arenas.
        """
        stats = stats if stats is not None else ExecStats()
        tr = self.tracer
        params = params if params is not None else self.params
        if len(graphs) != self.n_shards:
            raise ValueError(f"expected {self.n_shards} graphs (one per "
                             f"shard, None for idle), got {len(graphs)}")
        with tr.span("plan.pack", cat="plan"):
            packs = [self.pack_for(g, policy, stats) if g is not None
                     else None for g in graphs]
        specs = {p.spec for p in packs if p is not None}
        if not specs:
            return [None] * self.n_shards
        if any(p is None for p in packs) or len(specs) != 1:
            return self._run_fallback(graphs, policy, stats, params,
                                      shard_params)

        sspec = replace(packs[0].spec, n_shards=self.n_shards)
        with tr.span("plan.h2d", cat="plan"):
            idx = np.stack([p.idxpack_np for p in packs])
            aux = np.stack([_node_aux_np(g, p.aux_perm)
                            for g, p in zip(graphs, packs)])
            if shard_params is not None:
                # The AOT executable pins its input shardings; host-side
                # updates (e.g. the engine's slot writeback) leave the
                # stacked leaves on the default device, so normalize them
                # onto the mesh. A no-op when already placed.
                sharding = self.shard_sharding()
                shard_params = jax.tree.map(
                    lambda x: jax.device_put(x, sharding), shard_params)
        key, entry, compile_s = self._ensure_sharded_executable(sspec, params,
                                                                shard_params)
        if compile_s > 0:
            # Mirror the single-device path's per-pack compile accounting
            # (charged to the pack that triggered the build) so pack-level
            # stats stay comparable across both paths.
            packs[0].stats.n_compiles += 1
            packs[0].stats.compile_time_s += compile_s
        exe, pool, impls_pin = entry
        t1 = time.perf_counter()
        with tr.span("plan.dispatch", cat="plan"):
            arenas = exe(params, shard_params, idx, aux, pool)
        with tr.span("plan.block", cat="plan"):
            jax.block_until_ready(list(arenas.values()))
        dt = time.perf_counter() - t1
        if self.donate:
            self._exes[key] = (exe, arenas, impls_pin)
        if compile_s > 0:
            stats.lower_time += compile_s
            stats.n_compiles += 1
        stats.exec_time += dt
        stats.n_batches += sum(p.stats.n_steps for p in packs)
        stats.n_launches += 1
        self.n_sharded_dispatches += 1
        return [PlanResult(g, self.impls,
                           {k: v[s] for k, v in arenas.items()}, p.row_of)
                for s, (g, p) in enumerate(zip(graphs, packs))]
