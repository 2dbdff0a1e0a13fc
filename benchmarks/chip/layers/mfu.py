"""Whole step: model operations the traced window did, over its length,
as a share of the chip's peak (``peaks.json``). Moves the cell's rate."""


def read(ctx, variant: str):
    flops = ctx["model_flops"]
    span = ctx["window_s"]
    if not flops or span <= 0:
        return None
    return 100.0 * flops / span / ctx["peaks"]["flops_per_s"]
