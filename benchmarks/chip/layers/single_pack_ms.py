"""FSM policy and memory planner (``core/batching.py``, ``core/memplan.py``,
``core/plan.py``): host milliseconds per round spent packing single-shot
waves on the loop. The self time of the variant's span nested inside a
``round.single_pack`` span on the same thread, over the traced window's
``serve.round`` count. Moves ``ttft_p95_ms``.

- ``schedule``: ``plan.schedule``, the batching policy's schedule;
- ``lower``: ``plan.lower``, the row layout (declaration order on the
  loop), the lowering and the index pack.

A program that records no ``round.single_pack`` span reads nothing."""

SPANS = {"schedule": "plan.schedule", "lower": "plan.lower"}


def read(ctx, variant: str):
    name = SPANS[variant.partition(".")[2]]
    spans = ctx["spans"]
    rounds = [s for s in spans if s["name"] == "serve.round"]
    outer = [s for s in spans if s["name"] == "round.single_pack"]
    if not rounds or not outer:
        return None
    by_tid: dict = {}
    for s in outer:
        by_tid.setdefault(s["tid"], []).append((s["ts"], s["ts"] + s["dur"]))
    own = 0.0
    for s in spans:
        if s["name"] != name:
            continue
        if any(a <= s["ts"] and s["ts"] + s["dur"] <= b
               for a, b in by_tid.get(s["tid"], ())):
            own += s["self_us"]
    return own / len(rounds) / 1e3
