"""Row-gather staging kernel (the memcpy ED-Batch optimizes away).

When an operand is NOT contiguous in memory (unplanned layout, or a batch
the planner erased), the runtime must stage rows into a contiguous buffer
before the batched GEMM. On TPU this is a scalar-prefetch gather: the index
vector is prefetched to SMEM, and each grid step's BlockSpec index_map
selects the source row — the copy itself is the HBM->VMEM pipeline, with no
compute wasted. This is the TPU-native analogue of the CUDA gather kernel
DyNet emits (DESIGN.md, hardware adaptation #2).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gather_kernel(idx_ref, src_ref, out_ref):
    # The index_map already routed the right source row block here.
    out_ref[...] = src_ref[...]


def gather_rows_kernel(src, idx, *, block_d: int = 512,
                       interpret: bool = False):
    """src: (N, D); idx: (K,) int32 -> (K, D) == src[idx].

    Rows move through a free ``(N, 1, D)`` view with the row axis squeezed
    out of the block: Mosaic requires a block's last two dims to be
    (8, 128)-divisible or equal to the array's, and a ``(1, bd)`` block
    over ``(N, D)`` is neither, while ``(1, bd)`` over ``(N, 1, D)`` is."""
    N, D = src.shape
    K = idx.shape[0]
    bd = min(block_d, D)
    assert D % bd == 0
    grid = (K, D // bd)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, 1, bd),
                         lambda i, j, idx_ref: (idx_ref[i], 0, j)),
        ],
        out_specs=pl.BlockSpec((None, 1, bd),
                               lambda i, j, idx_ref: (i, 0, j)),
    )
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((K, 1, D), src.dtype),
        interpret=interpret,
    )(idx.astype(jnp.int32), src.reshape(N, 1, D))
    return out.reshape(K, D)


def gather_rows(src, idx, *, interpret: bool = False):
    """Backend-dispatching row gather: ``src[idx]`` along axis 0.

    Both compiled-plan executors (core/plan.py) route every unplanned or
    runtime-indexed operand here — the bucketed path's index vectors are
    traced operands, which the scalar-prefetch kernel supports natively.
    On TPU, sources whose flattened row length is lane-aligned use the
    Pallas kernel (>2-D element shapes gather as flat rows and reshape
    back); everything else (CPU/GPU backends, ragged row lengths) lowers to
    ``jnp.take``, which XLA fuses into the surrounding single-dispatch
    program.
    """
    idx = jnp.asarray(idx, jnp.int32)
    if jax.default_backend() == "tpu" and src.ndim >= 2:
        D = int(np.prod(src.shape[1:]))
        if D % 128 == 0:
            # Lane-aligned rows only (128 = TPU lane width); pick the
            # largest block that still divides D so the tiling assert holds.
            bd = 512 if D % 512 == 0 else 128
            flat = src.reshape(src.shape[0], D)
            out = gather_rows_kernel(flat, idx, block_d=bd,
                                     interpret=interpret)
            return out.reshape((idx.shape[0],) + src.shape[1:])
    return jnp.take(src, idx, axis=0)
