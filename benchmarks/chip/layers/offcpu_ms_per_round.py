"""Round loop, host thread (``serve/engine.py``): milliseconds per round
in which the thread that runs the rounds was off the CPU. Each
``serve.round`` span's wall time less its ``cpu_ms`` (the thread's
``time.thread_time`` over the round), over the traced window's rounds:
waiting on the device, on a lock, or for the host's scheduler. Moves
``itl_p95_ms``. A program whose rounds carry no ``cpu_ms`` reads nothing.

The reading is as fine as the host's thread clock. A host that ticks it in
10 ms (a sandboxed kernel) charges each round 0, 10 or 20 ms of CPU, so no
single round is resolved: the window's mean is a sampled estimate. With
ticks that fall independently of the rounds its standard error is at most
5 ms over the root of the round count (0.065 ms over 6,000 rounds)."""


def read(ctx, variant: str):
    rounds = [s for s in ctx["spans"] if s["name"] == "serve.round"]
    if not rounds or any("cpu_ms" not in s["args"] for s in rounds):
        return None
    return sum(s["dur"] / 1e3 - s["args"]["cpu_ms"]
               for s in rounds) / len(rounds)
