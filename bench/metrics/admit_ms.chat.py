"""Mean time the scheduler took to admit a request: the ``engine.admit``
spans (slot choice, state reservation, the prefix-cache probe) that start
in the traced window.  The engine opens one only when a slot is free, so
each is an admission unless the pool refused the reservation."""
import engine_spans as es


def read(ctx):
    if ctx.get("kind") != "serve":
        return None
    spans = es.in_window(ctx["events"], "engine.admit", ctx["window"])
    return sum(e - s for s, e in spans) * 1e-6 / len(spans) if spans else None
