"""Admission and scheduler (``serve/queue.py``, ``serve/scheduler.py``):
95th percentile of the wait from the harness's submission stamp to the
engine's admission stamp ``t_admit``, over requests admitted in the
window. Moves ``ttft_p95_ms``."""

from harness import percentile


def read(ctx, variant: str):
    xs = ctx["run"].queue_wait
    return percentile(xs, 95) * 1e3 if xs else None
