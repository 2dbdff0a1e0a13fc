"""Round loop (``serve/engine.py``): host milliseconds per round, the time
inside ``serve.round`` spans outside their ``plan.block`` (the wait for
the device), over the traced window's rounds. Moves ``itl_p95_ms``."""


def read(ctx, variant: str):
    spans = ctx["spans"]
    rounds = [s for s in spans if s["name"] == "serve.round"]
    if not rounds:
        return None
    tids = {s["tid"] for s in rounds}
    block = sum(s["dur"] for s in spans
                if s["name"] == "plan.block" and s["tid"] in tids)
    return (sum(s["dur"] for s in rounds) - block) / len(rounds) / 1e3
