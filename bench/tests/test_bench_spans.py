"""The readers of the engine's span tree and of the QAD step's phases.

``phase_map`` is checked on module text in the form the chip's compiler
prints it, and ``program_phases`` on the program's own step compiled here
at a tiny size.  The readers are checked on traces recorded on the chip
(``data/``: a slice of a traced window of each cell, as
``trace_events.read_xplane`` returns it, the QAD slice with the phase of
each op's instruction); the readers the benchmark had before read the
traces recorded before the span tree and the phases existed as they did
then, and the new ones find nothing there.
"""
import gzip
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import engine_spans  # noqa: E402
import harness  # noqa: E402
import peaks  # noqa: E402
import qad_phases  # noqa: E402
import trace_events as te  # noqa: E402
import weights  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
V5E = peaks.peaks("TPU v5 lite")
PHASES = ("teacher", "student_fwd", "student_bwd", "kl", "optimizer")
NEW = ("decode_host_ms", "admit_ms.chat") + tuple(
    f"{p}_ms.train" for p in PHASES)


def load(name):
    with gzip.open(DATA / f"{name}.json.gz", "rt") as f:
        return json.load(f)


def window(ev):
    return (te.spans(ev, "bench.window")[0][0],
            max(e[3] + e[4] for e in te.ops(ev)))


def read(name, ctx):
    return harness.load_metric(name).read(ctx)


# ---------------------------------------------------------------------------
# phase_map
# ---------------------------------------------------------------------------

HLO = """\
HloModule jit_step, is_scheduled=true

%fused_computation.1 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0:T(256)} parameter(0)
  ROOT %exp.3 = f32[8]{0:T(256)} exponential(%param_0.1), metadata={op_name="jit(step)/jvp(qad.kl)/jit(log_softmax)/exp" stack_frame_id=7}
}

%region_0.2 (arg_tuple.1: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg_tuple.1 = (s32[], f32[8]{0}) parameter(0)
  %get-tuple-element.5 = f32[8]{0} get-tuple-element(%arg_tuple.1), index=1
  %copy.9 = f32[8]{0} copy(%get-tuple-element.5)
  %multiply.4 = f32[8]{0} multiply(%copy.9, %copy.9), metadata={op_name="jit(step)/transpose(jvp(qad.student))/while/body/mul"}
  ROOT %tuple.2 = (s32[], f32[8]{0}) tuple(%get-tuple-element.5, %multiply.4)
}

ENTRY %main.10 (state_student.1: f32[8], batch_tokens.1: s32[8]) -> (f32[8]) {
  %state_student.1 = f32[8]{0} parameter(0), metadata={op_name="state.student"}
  %copy.1 = f32[8]{0} copy(%state_student.1)
  %dot.5 = f32[8]{0} multiply(%copy.1, %copy.1), metadata={op_name="jit(step)/jvp(qad.student)/dot_general"}
  %add.6 = f32[8]{0} add(%dot.5, %dot.5), metadata={op_name="jit(step)/jvp(qad.teacher)/add"}
  %fusion.2 = f32[8]{0} fusion(%add.6), kind=kLoop, calls=%fused_computation.1
  %tuple.3 = (s32[], f32[8]{0}) tuple(%fusion.2, %fusion.2)
  %while.4 = (s32[], f32[8]{0}) while(%tuple.3), condition=%region_0.2, body=%region_0.2, metadata={op_name="jit(step)/transpose(jvp(qad.student))/while"}
  %sqrt.7 = f32[8]{0} sqrt(%add.6), metadata={op_name="jit(step)/qad.optimizer/sqrt"}
  ROOT %reduce.8 = f32[] reduce(%sqrt.7, %constant.1), to_apply=%region_0.2, metadata={op_name="jit(step)/reduce_sum"}
}
"""


def test_phase_map_names_each_instruction_by_its_scope():
    pm = qad_phases.phase_map(HLO)
    assert pm["dot.5"] == "student_fwd"
    assert pm["multiply.4"] == "student_bwd"
    assert pm["while.4"] == "student_bwd"
    assert pm["add.6"] == "teacher"
    assert pm["sqrt.7"] == "optimizer"
    # no op_name of its own: the computation it calls, its operand, the
    # loop it is in, the instruction that uses it
    assert pm["fusion.2"] == "kl"
    assert pm["reduce.8"] == "student_bwd"       # what it calls
    assert pm["tuple.3"] == "kl"                 # its operand
    assert pm["copy.9"] == "student_bwd"         # its loop
    assert pm["copy.1"] == "student_fwd"         # its user
    assert set(pm.values()) <= set(PHASES) | {"unscoped"}


def test_program_phases_of_the_programs_step(monkeypatch):
    """The QAD step as the cell builds it, at a tiny size: every phase has
    instructions, and almost none is left without one."""
    cell = harness.load_cell("qwen05b-qad")
    cell["config"] = dict(cell["config"], hidden_size=64,
                          intermediate_size=96, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=2,
                          vocab_size=512)
    cell["mix"] = dict(cell["mix"], seq_len=64)
    monkeypatch.setattr(harness, "load_cell", lambda name: cell)
    pm = qad_phases.program_phases.__wrapped__("qwen05b-qad")
    counts = {p: sum(v == p for v in pm.values()) for p in PHASES}
    assert all(counts.values()), counts
    assert sum(v == "unscoped" for v in pm.values()) <= 0.05 * len(pm)
    # without a workload there is no step to read
    assert qad_phases.program_phases.__wrapped__(None) is None


# ---------------------------------------------------------------------------
# the readers the benchmark had, on the traces recorded before
# ---------------------------------------------------------------------------

def old_ctx(cell):
    ev = load(f"trace_{cell}")
    dims = weights.dims_of(harness.load_cell(cell)["config"])
    if cell == "qwen05b-qad":
        return {"kind": "train", "events": ev, "window": window(ev),
                "dims": dims, "peaks": V5E, "tokens": 4096, "seq_len": 4096}
    return {"kind": "serve", "events": ev, "window": window(ev),
            "dims": dims, "peaks": V5E, "slots": 32,
            "decode_contexts": [[300 + 40 * i for i in range(32)]],
            "queue_waits": [0.5, 1.5], "prefills": [(0.0, 512)]}


@pytest.mark.parametrize("cell,metric,value", [
    ("qwen05b-qad", "mfu.train", 226.65166581937518),
    ("qwen05b-qad", "idle_share.train", 1.3831042786160541),
    ("qwen05b-chat", "queue_wait_ms.chat", 1000.0),
    ("qwen05b-chat", "prefill_ms_per_ktok.chat", None),
    ("qwen05b-chat", "decode_step_ms", 296.999412),
    ("qwen05b-chat", "mfu.decode", 0.055686164481747236),
    ("qwen05b-chat", "nvfp4_matmul_roofline", 8.153003141540806),
    ("qwen05b-chat", "paged_attention_roofline", 3.175268594326019),
    ("qwen05b-chat", "idle_share.decode", 1.0734812498551238),
])
def test_the_readers_before_read_as_they_did(cell, metric, value):
    got = read(metric, old_ctx(cell))
    assert got == (value if value is None else pytest.approx(value,
                                                             rel=1e-12))


@pytest.mark.parametrize("cell", ["qwen05b-qad", "qwen05b-chat"])
def test_the_new_readers_find_nothing_in_a_trace_without_them(cell):
    """A program without the span tree and the phases (the parent of this
    change) gives the new readers nothing to read, and they say so."""
    ctx = old_ctx(cell)
    assert all(read(m, ctx) is None for m in NEW)


@pytest.mark.parametrize("metric", [
    m["name"] for m in json.loads(
        (Path(__file__).resolve().parents[2] / "BENCHMARK.json").read_text()
    )["per_layer"]])
def test_every_per_layer_metric_has_a_reader(metric):
    assert callable(harness.load_metric(metric).read)


# ---------------------------------------------------------------------------
# the new readers, on traces recorded with the span tree and the phases
# ---------------------------------------------------------------------------

def chat_spans_ctx():
    ev = load("trace_qwen05b-chat-spans")
    return {"kind": "serve", "events": ev, "window": window(ev)}


def test_decode_host_ms_reads_a_recorded_round():
    """The decode step's span less its wait, plus the emit that follows
    it; a step whose wait the slice cut off is left out."""
    ctx = chat_spans_ctx()
    ev = ctx["events"]
    lo, hi = ctx["window"]
    [(s, e)] = [x for x in te.spans(ev, "engine.decode_step")
                if lo <= x[0] < hi]
    [(ws, we)] = [x for x in te.spans(ev, "engine.decode.wait")
                  if s <= x[0] < e]
    [(ms, me)] = [x for x in te.spans(ev, "engine.decode.emit") if x[0] >= e]
    want = ((e - s) - (we - ws) + (me - ms)) * 1e-6
    assert read("decode_host_ms", ctx) == pytest.approx(want)
    assert read("decode_host_ms", ctx) == pytest.approx(46.628696)
    # the readers the benchmark had read the step's span as they did
    assert read("decode_step_ms", ctx) == pytest.approx((e - s) * 1e-6)


def test_admit_ms_is_the_mean_admit_span_in_the_window():
    ev = {"device": [], "host": [["engine.admit", 100.0, 2e5],
                                 ["engine.admit", 5e6, 1e5],
                                 ["engine.admit", 9e9, 7e6]]}
    ctx = {"kind": "serve", "events": ev, "window": (0.0, 1e9)}
    assert read("admit_ms.chat", ctx) == pytest.approx(0.15)
    # the recorded round admitted nobody
    assert read("admit_ms.chat", chat_spans_ctx()) is None


def test_gaps_inside_a_step_are_named_by_its_children():
    """With ``GAP_LABELS`` the breakdown names each of the ten longest
    gaps by the innermost engine span over its midpoint: a child of the
    step, or "none" outside every ``engine.step``."""
    ctx = chat_spans_ctx()
    ev = ctx["events"]
    gaps = te.idle_gaps(ev, ctx["window"], engine_spans.GAP_LABELS)
    labels = [g[0] for g in gaps]
    children = set(engine_spans.GAP_LABELS.values()) - {"decode step",
                                                         "step"}
    assert set(labels) <= children | {"none"}
    assert {"decode.wait", "decode.emit"} <= set(labels)
    # the old map names the same gaps by the step alone
    old = te.idle_gaps(ev, ctx["window"], {"engine.prefill": "prefill",
                                           "engine.decode_step":
                                           "decode step"})
    assert [g[1] for g in old] == [g[1] for g in gaps]


def qad_phases_ctx():
    ev = load("trace_qwen05b-qad-phases")
    return {"kind": "train", "events": ev, "window": window(ev),
            "qad_phases": ev["qad_phases"]}


@pytest.mark.parametrize("phase,ms", [
    ("teacher", 39.233709), ("student_fwd", 0.081171),
    ("student_bwd", 911.685824), ("kl", 0.002082), ("optimizer", 0.000325)])
def test_qad_phase_readers_on_a_recorded_window(phase, ms):
    assert read(f"{phase}_ms.train", qad_phases_ctx()) == pytest.approx(ms)


def test_qad_phases_add_up_to_the_busy_time():
    """Own times tile the busy time, so the phases (with ``unscoped``)
    add up to it per step; a map that lacks the window's ops reads
    nothing."""
    ctx = qad_phases_ctx()
    ev, win = ctx["events"], ctx["window"]
    per = qad_phases.step_phases(ev, win, ctx["qad_phases"])
    steps = sum(win[0] <= s < win[1] for s, _ in te.spans(ev, "bench.feed"))
    assert sum(per.values()) == pytest.approx(te.busy(ev, [win]) / steps)
    assert per["unmatched"] == 0.0
    partial = {k: v for k, v in ctx["qad_phases"].items()
               if v != "student_bwd"}
    assert read("teacher_ms.train", dict(ctx, qad_phases=partial)) is None
