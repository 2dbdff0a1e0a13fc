"""Python runtime: milliseconds per round spent in garbage collections of
any generation, on any thread, while a round ran. The ``gc_ms`` of each
``serve.round`` span (the tracer's GC hook sums every collection's time),
over the traced window's rounds. Moves ``tokens_per_s``. A program whose
rounds carry no ``gc_ms`` reads nothing."""


def read(ctx, variant: str):
    rounds = [s for s in ctx["spans"] if s["name"] == "serve.round"]
    if not rounds or any("gc_ms" not in s["args"] for s in rounds):
        return None
    return sum(s["args"]["gc_ms"] for s in rounds) / len(rounds)
