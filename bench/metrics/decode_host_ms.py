"""Host time of a decode round, over the decode steps that start in the
traced window: the ``engine.decode_step`` span less its
``engine.decode.wait`` child (the host blocked on the device), plus the
``engine.decode.emit`` span between the step's end and the next step
(``engine_spans``)."""
import engine_spans as es


def read(ctx):
    if ctx.get("kind") != "serve":
        return None
    ev, win = ctx["events"], ctx["window"]
    steps = es.in_window(ev, "engine.decode_step", win)
    waits = es.child_time(steps, es.in_window(ev, "engine.decode.wait", win))
    after = [(e, nxt) for (_, e), (nxt, _) in zip(
        steps, steps[1:] + [(float("inf"), None)])]
    emits = es.child_time(after, es.in_window(ev, "engine.decode.emit", win))
    host = [(e - s) - w + (m or 0.0)
            for (s, e), w, m in zip(steps, waits, emits) if w is not None]
    return sum(host) * 1e-6 / len(host) if host else None
