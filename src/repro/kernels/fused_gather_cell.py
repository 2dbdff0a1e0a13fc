"""Fused gather→LSTM-cell Pallas kernel (DESIGN.md deviation #4).

The bucketed plan executor makes *every* operand a runtime row-gather, so
on the dominant gather-fallback steps the unfused pipeline materializes
three gathered operand buffers in HBM (x, h, c rows) before the cell's
batched GEMM ever runs. This kernel removes the round-trip: the three
index vectors are scalar-prefetched to SMEM, each grid step's BlockSpec
``index_map`` routes the operand *rows* straight out of the source arenas
into VMEM, and the cell — one (1, E+H) x (E+H, 4H) gate matmul plus the
VPU state update, exactly :mod:`repro.kernels.fused_cell` — consumes them
without an intermediate HBM buffer. Outputs are dense ``(B, H)`` blocks;
the scatter back into the output arenas stays an XLA ``.at[idx].set`` the
compiler fuses with the surrounding single-dispatch program.

Weight layout matches ``fused_cell``: ``w`` is ``(E+H, 4H)`` with gate
columns blocked ``[i|f|g|o]``; ``b`` is ``(4H,)``. The dispatching wrapper
falls back to a pure-jnp gather+cell (which XLA fuses on its own) off-TPU
or for lane-misaligned hidden sizes.

Row operands move through free ``(N, 1, D)`` views with the row axis
squeezed out of the block (see ``gather_batch.gather_rows_kernel``: Mosaic
refuses a ``(1, D)`` block over ``(N, D)``). The gate matmul runs at
:data:`repro.core.ops.MATMUL_PRECISION`, the precision of every other tier.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.ops import MATMUL_PRECISION


def _kernel(ix_ref, ih_ref, ic_ref, x_ref, h_ref, c_ref, w_ref, b_ref,
            h_out_ref, c_out_ref, *, hidden: int):
    # index_maps already routed this program's gathered rows here.
    xh = jnp.concatenate([x_ref[...], h_ref[...]], axis=-1)   # (1, E+H)
    y = jax.lax.dot_general(xh, w_ref[...], (((1,), (0,)), ((), ())),
                            precision=MATMUL_PRECISION,
                            preferred_element_type=jnp.float32)
    y = y + b_ref[...].astype(jnp.float32)                    # (1, 4H)
    i = jax.nn.sigmoid(y[:, 0 * hidden:1 * hidden])
    f = jax.nn.sigmoid(y[:, 1 * hidden:2 * hidden])
    g = jnp.tanh(y[:, 2 * hidden:3 * hidden])
    o = jax.nn.sigmoid(y[:, 3 * hidden:4 * hidden])
    c_new = f * c_ref[...].astype(jnp.float32) + i * g
    c_out_ref[...] = c_new.astype(c_out_ref.dtype)
    h_out_ref[...] = (o * jnp.tanh(c_new)).astype(h_out_ref.dtype)


def fused_gather_lstm_cell_kernel(x_src, h_src, c_src, ix, ih, ic, w, b, *,
                                  interpret: bool = False):
    """x_src: (Nx, E); h_src: (Nh, H); c_src: (Nc, H); ix/ih/ic: (B,) int32;
    w: (E+H, 4H) gate-blocked [i|f|g|o]; b: (4H,) ->
    (h', c') each (B, H) == lstm(concat(x_src[ix], h_src[ih]), c_src[ic])."""
    B = ix.shape[0]
    E = x_src.shape[1]
    H = h_src.shape[1]
    kernel = functools.partial(_kernel, hidden=H)

    out_row = pl.BlockSpec((None, 1, H), lambda i, *_: (i, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((None, 1, E), lambda i, ix, ih, ic: (ix[i], 0, 0)),
            pl.BlockSpec((None, 1, H), lambda i, ix, ih, ic: (ih[i], 0, 0)),
            pl.BlockSpec((None, 1, H), lambda i, ix, ih, ic: (ic[i], 0, 0)),
            pl.BlockSpec((E + H, 4 * H), lambda i, *_: (0, 0)),
            pl.BlockSpec((1, 4 * H), lambda i, *_: (0, 0)),
        ],
        out_specs=[out_row, out_row],
    )
    h_out, c_out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, 1, H), h_src.dtype),
                   jax.ShapeDtypeStruct((B, 1, H), h_src.dtype)],
        interpret=interpret,
    )(ix.astype(jnp.int32), ih.astype(jnp.int32), ic.astype(jnp.int32),
      x_src.reshape(-1, 1, E), h_src.reshape(-1, 1, H),
      c_src.reshape(-1, 1, H), w, b.reshape(1, 4 * H))
    return h_out.reshape(B, H), c_out.reshape(B, H)


def _jnp_fallback(x_src, h_src, c_src, ix, ih, ic, w, b):
    """Gather + fused gate math in plain jnp — XLA fuses the gathers into
    the GEMM on CPU/GPU, so no extra HBM buffer survives either."""
    xh = jnp.concatenate([jnp.take(x_src, ix, axis=0),
                          jnp.take(h_src, ih, axis=0)], axis=-1)
    H = h_src.shape[1]
    y = jnp.matmul(xh, w, precision=MATMUL_PRECISION) + b
    i = jax.nn.sigmoid(y[:, 0 * H:1 * H])
    f = jax.nn.sigmoid(y[:, 1 * H:2 * H])
    g = jnp.tanh(y[:, 2 * H:3 * H])
    o = jax.nn.sigmoid(y[:, 3 * H:4 * H])
    c_new = f * jnp.take(c_src, ic, axis=0) + i * g
    return o * jnp.tanh(c_new), c_new


def fused_gather_lstm_cell(x_src, h_src, c_src, ix, ih, ic, w, b, *,
                           interpret: bool | None = None):
    """Backend-dispatching fused gather→cell.

    ``interpret=None`` picks the Pallas kernel on TPU for lane-aligned
    widths and the jnp fallback elsewhere; ``interpret=True`` forces the
    Pallas kernel in interpret mode (how CI exercises the kernel body).
    """
    ix = jnp.asarray(ix, jnp.int32)
    ih = jnp.asarray(ih, jnp.int32)
    ic = jnp.asarray(ic, jnp.int32)
    E, H = x_src.shape[1], h_src.shape[1]
    if interpret is None:
        on_tpu = jax.default_backend() == "tpu"
        if not (on_tpu and E % 128 == 0 and H % 128 == 0):
            return _jnp_fallback(x_src, h_src, c_src, ix, ih, ic, w, b)
        interpret = False
    return fused_gather_lstm_cell_kernel(x_src, h_src, c_src, ix, ih, ic,
                                         w, b, interpret=interpret)
