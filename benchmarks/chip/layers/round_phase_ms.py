"""Round loop (``serve/engine.py``, ``core/plan.py``): host milliseconds
per round in one phase of the round. The self time of the phase's spans on
the thread that runs the rounds (the ``tid`` of the ``serve.round`` spans),
over the traced window's ``serve.round`` count. Spans stamped ``overlap``
count too: the next round's speculative pack runs on that thread, inside
the round it overlaps. Moves ``itl_p95_ms``.

- ``pack``: ``round.pack``, ``round.feed_stage``, ``plan.pack``;
- ``dispatch``: ``round.dispatch`` (the engine's side of the dispatch),
  ``round.lookup``, ``plan.h2d``, ``plan.dispatch``;
- ``commit``: ``round.commit``;
- ``readback``: ``round.readback``, ``plan.block``.

A program that records no ``round.commit`` span has no phase split, and
reads nothing."""

PHASES = {
    "pack": ("round.pack", "round.feed_stage", "plan.pack"),
    "dispatch": ("round.dispatch", "round.lookup", "plan.h2d",
                 "plan.dispatch"),
    "commit": ("round.commit",),
    "readback": ("round.readback", "plan.block"),
}


def read(ctx, variant: str):
    names = PHASES[variant.partition(".")[2]]
    spans = ctx["spans"]
    rounds = [s for s in spans if s["name"] == "serve.round"]
    loop = {s["tid"] for s in rounds}
    mine = [s for s in spans if s["tid"] in loop]
    if not rounds or not any(s["name"] == "round.commit" for s in mine):
        return None
    own = sum(s["self_us"] for s in mine if s["name"] in names)
    return own / len(rounds) / 1e3
