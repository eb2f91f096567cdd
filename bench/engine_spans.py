"""The serving engine's span tree as a traced window holds it.

The engine writes its spans on the profiler's clock (``Tracer.annotate``),
each child closed before its parent:

    engine.step                     one ``Engine.step()``
      engine.admit                  admission of one request
      engine.prefill                one request's prefill
      engine.first_token            its first token sampled and emitted
      engine.decode.grow            on-demand growth and preemption
      engine.decode_step            one batched decode
        engine.decode.inputs        per-slot arrays and the block table
        engine.decode.dispatch      the jitted step enqueued
        engine.decode.sample        the sampler enqueued
        engine.decode.wait          blocked on the device for the tokens
      engine.decode.emit            tokens stamped and emitted

(The speculative engine emits inside its ``engine.decode_step``.)

``GAP_LABELS`` names an idle gap of the device by the innermost of these
spans open at its midpoint when given to ``trace_events.idle_gaps``, which
takes the first label, in the map's order, whose span holds the midpoint:
children come before their parents, and a gap outside ``engine.step`` is
"none".
"""
from __future__ import annotations

import bisect

import trace_events as te

GAP_LABELS = {
    "engine.decode.inputs": "decode.inputs",
    "engine.decode.dispatch": "decode.dispatch",
    "engine.decode.sample": "decode.sample",
    "engine.decode.wait": "decode.wait",
    "engine.decode.emit": "decode.emit",
    "engine.decode.grow": "decode.grow",
    "engine.admit": "admit",
    "engine.prefill": "prefill",
    "engine.first_token": "first_token",
    "engine.decode_step": "decode step",
    "engine.step": "step",
}


def in_window(events: dict, name: str, window) -> list:
    """(start, end) of the ``name`` spans that start in ``window``."""
    lo, hi = window
    return [(s, e) for s, e in te.spans(events, name) if lo <= s < hi]


def child_time(parents: list, children: list) -> list:
    """For each parent interval, the time of the child spans that start
    in it (None where it holds none)."""
    starts = [s for s, _ in children]
    out = []
    for s, e in parents:
        i, j = bisect.bisect_left(starts, s), bisect.bisect_left(starts, e)
        out.append(sum(ce - cs for cs, ce in children[i:j]) if j > i
                   else None)
    return out
