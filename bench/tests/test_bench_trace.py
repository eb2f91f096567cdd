"""The reduction from a profiler trace to per-layer metrics, on traces
recorded on the chip (``data/``: a slice of a traced window of each
cell, as ``trace_events.read_xplane`` returns it).  Busy time is checked
against a count on a fine time grid, which shares no code with the
interval union under test."""
import gzip
import json
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import flops  # noqa: E402
import harness  # noqa: E402
import peaks  # noqa: E402
import trace_events as te  # noqa: E402
import weights  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
V5E = peaks.peaks("TPU v5 lite")


def load(cell):
    with gzip.open(DATA / f"trace_{cell}.json.gz", "rt") as f:
        return json.load(f)


def grid_busy(evs, lo, hi, step=100.0):
    """Nanoseconds of [lo, hi) covered by some event, on a 100 ns grid."""
    covered = np.zeros(int((hi - lo) / step) + 1, bool)
    for e in evs:
        a = max(int((e[3] - lo) / step), 0)
        b = min(int(np.ceil((e[3] + e[4] - lo) / step)), covered.size)
        covered[a:b] = True
    return covered.sum() * step


def qad_window(ev):
    lo = te.spans(ev, "bench.window")[0][0]
    return lo, max(e[3] + e[4] for e in te.ops(ev))


def test_busy_is_the_union_of_op_intervals():
    ev = load("qwen05b-qad")
    lo, hi = qad_window(ev)
    want = grid_busy(te.ops(ev), lo, hi) * 1e-9
    assert te.busy(ev, [(lo, hi)]) == pytest.approx(want, rel=2e-3)


def test_self_times_add_up_to_busy_time():
    """Ops nest on the ops line (a loop holds its body's ops): their own
    times, children left out, tile the busy time exactly."""
    ev = load("qwen05b-qad")
    lo, hi = qad_window(ev)
    st = te.self_times(te.ops(ev))
    assert all(t >= 0 for _, t in st)
    assert sum(t for _, t in st) * 1e-9 == pytest.approx(
        te.busy(ev, [(lo, hi)]), rel=1e-6)
    top = te.top_ops(te.ops(ev))
    assert len(top) == 10 and all(" " not in n for n, _ in top)
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)


def test_train_readers():
    ev = load("qwen05b-qad")
    lo, hi = qad_window(ev)
    cell = harness.load_cell("qwen05b-qad")
    dims = weights.dims_of(cell["config"])
    ctx = {"kind": "train", "events": ev, "window": (lo, hi), "dims": dims,
           "peaks": V5E, "tokens": 4096, "seq_len": 4096}
    idle = harness.load_metric("idle_share.train").read(ctx)
    assert idle == pytest.approx(
        100 * (1 - grid_busy(te.ops(ev), lo, hi) / (hi - lo)), abs=0.2)
    mfu = harness.load_metric("mfu.train").read(ctx)
    want = 100 * flops.qad_step_per_token(dims, 4096) * 4096 \
        / ((hi - lo) * 1e-9) / V5E["bf16_flops"]
    assert mfu == pytest.approx(want)
    # a serving reader finds nothing to read in a training trace
    assert harness.load_metric("decode_step_ms").read(ctx) is None


def test_idle_gaps_are_named_by_the_host_span_they_fall_in():
    ev = load("qwen05b-qad")
    lo, hi = qad_window(ev)
    gaps = te.idle_gaps(ev, (lo, hi), {"bench.feed": "feed"})
    assert 0 < len(gaps) <= 10
    assert {g[0] for g in gaps} <= {"feed", "none"}
    assert sum(g[1] for g in gaps) <= (hi - lo) * 1e-9 \
        - te.busy(ev, [(lo, hi)]) + 1e-9


def chat_ctx():
    ev = load("qwen05b-chat")
    cell = harness.load_cell("qwen05b-chat")
    dims = weights.dims_of(cell["config"])
    lo = te.spans(ev, "bench.window")[0][0]
    hi = max(e[3] + e[4] for e in te.ops(ev))
    contexts = [[300 + 40 * i for i in range(32)]]     # one decode step
    return {"kind": "serve", "events": ev, "window": (lo, hi), "dims": dims,
            "peaks": V5E, "slots": 32, "decode_contexts": contexts,
            "queue_waits": [0.5, 1.5], "prefills": [(0.0, 512)]}


def test_decode_step_readers():
    ctx = chat_ctx()
    ev = ctx["events"]
    [(s, e)] = te.spans(ev, "engine.decode_step")
    read = lambda m: harness.load_metric(m).read(ctx)
    assert read("decode_step_ms") == pytest.approx((e - s) * 1e-6)
    inside = [x for x in te.ops(ev) if s <= x[3] < e]
    idle = 100 * (1 - grid_busy(inside, s, e) / (e - s))
    assert read("idle_share.decode") == pytest.approx(idle, abs=0.2)
    want = 100 * flops.decode_step(ctx["dims"], ctx["decode_contexts"][0]) \
        / ((e - s) * 1e-9) / V5E["bf16_flops"]
    assert read("mfu.decode") == pytest.approx(want)
    assert read("queue_wait_ms.chat") == pytest.approx(1000.0)
    # a training reader finds nothing to read in a serving trace
    assert harness.load_metric("mfu.train").read(ctx) is None


@pytest.mark.parametrize("kernel,calls", [("nvfp4_matmul", 5 * 24),
                                          ("paged_attention", 24)])
def test_kernel_roofline_reads_the_kernels_own_events(kernel, calls):
    """Each GEMM and attention call of the step is one event named after
    its kernel; the share is the least time over their device time."""
    ctx = chat_ctx()
    ev = ctx["events"]
    [(s, e)] = te.spans(ev, "engine.decode_step")
    evs = [x for x in te.ops(ev) if s <= x[3] < e
           and te.kernel_of(x) == kernel]
    assert len(evs) == calls
    dims, (contexts,) = ctx["dims"], ctx["decode_contexts"]
    if kernel == "nvfp4_matmul":
        work = [flops.gemm_call(32, k, n)
                for k, n in flops.layer_gemms(dims).values()] * 24
    else:
        work = [flops.paged_attention_call(dict(dims, n_layers=1),
                                           contexts)] * 24
    least = sum(max(f / V5E["bf16_flops"], b / V5E["hbm_bytes_s"])
                for f, b in work)
    share = harness.load_metric(f"{kernel}_roofline").read(ctx)
    assert share == pytest.approx(100 * least / (sum(x[4] for x in evs)
                                                 * 1e-9))
    assert 0 < share < 100


def test_prefill_reader_counts_spans_that_start_in_the_window():
    """Spans that start outside the window are left out; the tokens are
    those of the requests admitted in it, which the harness passes."""
    ev = {"device": [], "host": [["engine.prefill", 100.0, 2e6],
                                 ["engine.prefill", 5e6, 3e6],
                                 ["engine.prefill", 9e9, 7e6]]}
    ctx = {"kind": "serve", "events": ev, "window": (0.0, 1e9),
           "prefills": [(1.0, 512), (2.0, 1536)]}
    read = harness.load_metric("prefill_ms_per_ktok.chat").read
    assert read(ctx) == pytest.approx(5.0 / 2.048)
    assert read(dict(ctx, prefills=[])) is None
    assert read(dict(ctx, window=(1e10, 2e10))) is None
