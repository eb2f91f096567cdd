"""Device time of the QAD step's phases in a traced window.

The program names the step's phases with ``jax.named_scope``
(``repro.core.qad.PHASES``).  The compiled module's text carries each
instruction's scope in ``metadata={op_name="jit(step)/jvp(qad.student)/..."}``
and a device op event is named after its instruction, so device time joins
to a phase by instruction name:

  * ``phase_map(hlo_text)``: {instruction: phase}, the phase being the
    ``qad.*`` scope of the instruction's op_name, with ``student`` split
    into ``student_fwd`` and ``student_bwd`` (op_names under
    ``transpose(``, the backward pass).  An instruction without an op_name
    of its own (a fusion, or a copy XLA put in) takes the commonest phase
    of the computations it calls, else that of its first operand with one,
    else that of the instruction that calls its computation (a loop's
    body), else the commonest of its users' (a copy of an argument); one
    with none of these is ``unscoped``;
  * ``step_phases(events, window, phases)``: seconds of device time of
    each phase per step of the window (the ops' own time, children left
    out; the steps are the window's ``bench.feed`` spans), with
    ``unmatched`` for op events whose name the module does not hold;
  * ``program_phases()``: the map of the step this run traced, compiled
    again on abstract arguments after the window (the persistent cache
    serves it); None where the program names no phases.

A reader finds the map in ``ctx["qad_phases"]`` where the harness put it
there, and otherwise asks ``program_phases()``.
"""
from __future__ import annotations

import collections
import functools
import re
import sys

import trace_events as te

INSTR = re.compile(r"\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
COMPUTATION = re.compile(r"(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
OP_NAME = re.compile(r'op_name="([^"]*)"')
CALLS = re.compile(r"(?:calls|to_apply|body|condition|branch_computations"
                   r"|called_computations)=(\{[^}]*\}|%?[\w.\-]+)")
OPERAND = re.compile(r"%([\w.\-]+)")
SCOPE = re.compile(r"qad\.(\w+)")
# most op events in the window whose names the module may lack
MAX_UNMATCHED = 0.01


def scope_phase(op_name: str) -> str | None:
    """The phase an op_name names, or None."""
    m = SCOPE.search(op_name)
    if m is None:
        return None
    if m.group(1) == "student":
        return "student_bwd" if "transpose(" in op_name else "student_fwd"
    return m.group(1)


def phase_map(hlo_text: str) -> dict:
    """{instruction name: phase} of a compiled module's text."""
    comps: dict = {}            # computation -> [(name, own, calls, operands)]
    body = None
    for line in hlo_text.splitlines():
        ins = INSTR.match(line)
        if ins is None:
            head = COMPUTATION.match(line)
            if head is not None and not line.startswith(" "):
                body = comps.setdefault(head.group(1), [])
            continue
        if body is None:
            continue
        name, rest = ins.groups()
        op = OP_NAME.search(rest)
        calls = [c.strip(" {}%") for m in CALLS.finditer(rest)
                 for c in m.group(1).split(",") if c.strip(" {}%")]
        # the type holds no "%": the first names are the operands
        operands = OPERAND.findall(CALLS.sub("", rest.split(", metadata=")[0]))
        body.append((name, scope_phase(op.group(1)) if op else None, calls,
                     operands))

    @functools.lru_cache(maxsize=None)
    def called_phase(comp: str) -> str | None:
        """The commonest phase named inside ``comp`` and what it calls."""
        count = collections.Counter()
        for _, own, calls, _ in comps.get(comp, ()):
            if own is not None:
                count[own] += 1
            for c in calls:
                p = called_phase(c)
                if p is not None:
                    count[p] += 1
        return count.most_common(1)[0][0] if count else None

    callers = {c: name for instrs in comps.values()
               for name, _, calls, _ in instrs for c in calls}
    users: dict = collections.defaultdict(list)
    for instrs in comps.values():
        for name, _, _, operands in instrs:
            for a in operands:
                users[a].append(name)
    out: dict = {}
    for _ in range(4):              # callers nest a few loops deep at most
        for comp, instrs in comps.items():
            for name, own, calls, operands in instrs:
                used_by = collections.Counter(
                    out.get(u) for u in users[name]
                    if out.get(u) not in (None, "unscoped"))
                found = [own] + [called_phase(c) for c in calls] \
                    + [out.get(a) for a in operands] \
                    + [out.get(callers.get(comp))] \
                    + [p for p, _ in used_by.most_common(1)]
                out[name] = next((p for p in found
                                  if p not in (None, "unscoped")), "unscoped")
    return out


_last: list = [None, None, None, None]    # events, window, phases, result


def step_phases(events: dict, window, phases: dict) -> dict | None:
    """{phase: device seconds per step} over the window's op events, or
    None where it holds no step or too many ops the map lacks.  The five
    readers ask for the same trace in turn: the last answer is kept."""
    if (_last[0] is events and _last[1] == tuple(window)
            and _last[2] is phases):
        return _last[3]
    lo, hi = window
    steps = sum(lo <= s < hi for s, _ in te.spans(events, "bench.feed"))
    evs = te.inside(te.ops(events), [(lo, hi)])
    n_planes = len({e[0] for e in evs})
    per: dict = collections.defaultdict(float)
    for e, t in te.self_times(evs):
        per[phases.get(e[2], "unmatched")] += t
    total = sum(per.values())
    out = None
    if steps and total and per["unmatched"] <= MAX_UNMATCHED * total:
        out = {k: v * 1e-9 / n_planes / steps for k, v in per.items()}
        print("[bench] qad phases, ms per step of "
              f"{steps}: " + " ".join(f"{k}={v * 1e3:.3f}"
                                      for k, v in sorted(out.items())),
              file=sys.stderr, flush=True)
    elif total:
        print(f"[bench] qad phases: {per['unmatched'] / total:.1%} of the "
              "window's op time has no instruction in the step's module",
              file=sys.stderr, flush=True)
    _last[:] = [events, tuple(window), phases, out]
    return out


def _workload() -> str | None:
    import argparse

    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--workload")
    return ap.parse_known_args(sys.argv[1:])[0].workload


@functools.lru_cache(maxsize=None)
def program_phases(workload: str | None = None) -> dict | None:
    """The phase map of the QAD step that ``train_cell`` ran for
    ``workload`` (this run's ``--workload`` by default), from its text
    compiled again on abstract arguments; None where the program's QAD
    step names no phases."""
    workload = workload or _workload()
    try:
        from repro.core.qad import PHASES  # noqa: F401
    except ImportError:
        return None
    if workload is None:
        return None
    import jax
    import jax.numpy as jnp

    import harness
    import traffic
    import train_cell
    import weights
    from repro.core import qad

    cell = harness.load_cell(workload)
    dims = weights.dims_of(cell["config"])
    cfg = train_cell.program_config(cell["config"], dims)
    opt, step = train_cell._program_step(cfg, cell["cell"]["lr"])
    one = jax.sharding.SingleDeviceSharding(jax.devices()[0])

    def init():
        p = weights.make(dims, jax.random.PRNGKey(0))
        return qad.TrainState(step=jnp.zeros((), jnp.int32), student=p,
                              teacher=jax.tree.map(jnp.copy, p),
                              opt_state=opt.init(p))

    batch = traffic.train_batch(cell["mix"], dims["vocab_size"],
                                cell["cell"]["batch"], 0, 0)
    args = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
        (jax.eval_shape(init), batch))
    return phase_map(step.lower(*args).compile().as_text())


def read(ctx: dict, phase: str) -> float | None:
    """Milliseconds of device time per step of one phase in the traced
    window, or None where there is nothing to read."""
    if ctx.get("kind") != "train":
        return None
    phases = ctx.get("qad_phases") or program_phases()
    per = phases and step_phases(ctx["events"], ctx["window"], phases)
    return 1e3 * per.get(phase, 0.0) if per else None
