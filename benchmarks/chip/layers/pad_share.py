"""Bucketed plan (``core/plan.py:pack_bucketed``): the share of the lanes
of the window's dispatched single-shot bucket programs that carry no real
node, ``100 * padded / (real + padded)``, summed over every step of every
dispatched spec (pad lanes of a step and whole pad steps alike). Read from
the engine's counters ``ServeStats.n_single_lanes_real`` and
``n_single_lanes_padded`` across the window. Moves ``ttft_p95_ms``. A
program without those counters, or a window with no such dispatch, reads
nothing."""


def read(ctx, variant: str):
    lanes = getattr(ctx["run"], "lanes", None)
    if not lanes or sum(lanes) <= 0:
        return None
    real, padded = lanes
    return 100.0 * padded / (real + padded)
