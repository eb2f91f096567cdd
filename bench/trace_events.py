"""Reading a profiler trace, and the reductions every per-layer metric
shares.

``read_xplane(dir)`` turns the ``.xplane.pb`` that ``jax.profiler`` wrote
into two plain lists, which is all the metric readers see (and all a
recorded test trace has to hold):

  * ``device``: [plane, line, name, start_ns, dur_ns] for each event on
    a TPU plane, ``name`` the HLO instruction's name (the event's name up
    to " = ", which is the whole instruction's text).  A Pallas kernel's
    instruction is named after the kernel (``nvfp4_matmul.27``,
    ``paged_attention.9``); the events carry no named scope;
  * ``host``:   [name, start_ns, dur_ns] for each host span whose name
    starts with ``bench.`` or ``engine.`` (the benchmark's own spans and
    the engine's ``TraceAnnotation``s).

Device and host events share the profiler's clock.
"""
from __future__ import annotations

import glob
import re
from pathlib import Path

HOST_PREFIXES = ("bench.", "engine.")
OPS_LINE = "XLA Ops"


def read_xplane(directory) -> dict:
    from jax.profiler import ProfileData

    files = glob.glob(str(Path(directory) / "**" / "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    device, host = [], []
    for plane in ProfileData.from_file(files[0]).planes:
        on_device = plane.name.startswith("/device:TPU")
        for line in plane.lines:
            for ev in line.events:
                if on_device:
                    device.append([plane.name, line.name,
                                   ev.name.split(" = ")[0].lstrip("%"),
                                   float(ev.start_ns), float(ev.duration_ns)])
                elif ev.name.startswith(HOST_PREFIXES):
                    host.append([ev.name, float(ev.start_ns),
                                 float(ev.duration_ns)])
    return {"device": device, "host": host}


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------


def spans(events: dict, name: str) -> list:
    """(start, end) of the host spans called ``name``, in order."""
    return sorted((s, s + d) for n, s, d in events["host"] if n == name)


def ops(events: dict, plane: str | None = None) -> list:
    """Device op events ([plane, line, name, start, dur]) on the ops line,
    of one plane or of all."""
    return [e for e in events["device"] if e[1] == OPS_LINE
            and (plane is None or e[0] == plane)]


def planes(events: dict) -> list:
    return sorted({e[0] for e in events["device"] if e[1] == OPS_LINE})


def union(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``intervals`` clipped to [lo, hi]."""
    total, end = 0.0, lo
    for s, e in sorted(intervals):
        s, e = max(s, end), min(e, hi)
        if e > s:
            total += e - s
            end = e
    return total


def busy(events: dict, windows) -> float:
    """Seconds in which some op ran on the device inside ``windows``,
    averaged over the device planes."""
    ps = planes(events)
    if not ps:
        return 0.0
    total = 0.0
    for p in ps:
        iv = [(e[3], e[3] + e[4]) for e in ops(events, p)]
        total += sum(union(iv, lo, hi) for lo, hi in windows)
    return total / len(ps) * 1e-9


def inside(evs, windows) -> list:
    """The events that start inside one of ``windows``."""
    windows = sorted(windows)
    out = []
    for e in evs:
        for lo, hi in windows:
            if lo <= e[3] < hi:
                out.append(e)
                break
    return out


def kernel_of(event) -> str:
    """The kernel (or kind of XLA op) an op event belongs to: its
    instruction's name without the numeric suffix."""
    return re.sub(r"\.\d+$", "", event[2])


def self_times(evs) -> list:
    """(event, exclusive ns) of each op event: ops nest on a line (a loop
    holds the ops of its body), so a parent's time leaves out its
    children's."""
    out = []
    by_line: dict = {}
    for e in evs:
        by_line.setdefault((e[0], e[1]), []).append(e)
    for line in by_line.values():
        line.sort(key=lambda e: (e[3], -e[4]))
        stack: list = []             # [event, end, exclusive]
        for e in line:
            while stack and stack[-1][1] <= e[3]:
                out.append(tuple(stack.pop()[::2]))
            if stack:
                stack[-1][2] -= min(e[4], stack[-1][1] - e[3])
            stack.append([e, e[3] + e[4], e[4]])
        out += [tuple(x[::2]) for x in stack]
    return out


def top_ops(evs, n: int = 10) -> list:
    """[[name, seconds], ...] of the ``n`` op names that took most device
    time of their own (children left out), averaged over the planes."""
    per: dict = {}
    for e, t in self_times(evs):
        per[e[2]] = per.get(e[2], 0.0) + t
    n_planes = len({e[0] for e in evs}) or 1
    top = sorted(per.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / n_planes * 1e-9] for k, v in top]


def idle_gaps(events: dict, window, labels, n: int = 10) -> list:
    """The ``n`` longest stretches of the window in which no op ran on the
    device, each named by the host span it falls in (``labels``: span
    name -> label; "none" where no such span is open)."""
    ps = planes(events)
    if not ps:
        return []
    lo, hi = window
    iv = sorted((e[3], e[3] + e[4]) for e in ops(events, ps[0]))
    gaps, end = [], lo
    for s, e in iv:
        if s > end and end < hi:
            gaps.append((end, min(s, hi)))
        end = max(end, e)
    if end < hi:
        gaps.append((end, hi))
    open_spans = [(s, e, labels[n_]) for n_ in labels
                  for s, e in spans(events, n_)]

    def label(g):
        mid = (g[0] + g[1]) / 2
        for s, e, lab in open_spans:
            if s <= mid < e:
                return lab
        return "none"

    gaps.sort(key=lambda g: g[0] - g[1])
    return [[label(g), (g[1] - g[0]) * 1e-9] for g in gaps[:n]]
