"""Device: the share of the traced window in which no operation ran on
the chip, from the profiler's trace. Moves the cell's rate."""


def read(ctx, variant: str):
    tr = ctx["trace"]
    if tr is None or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
