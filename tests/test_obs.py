"""Serving telemetry (repro.obs): metrics registry semantics, tracer span
lifecycle, schema validation of the exported artifacts, dispatch counters,
and the engine-level acceptance invariant — greedy tokens are BITWISE
identical with telemetry off, metrics-on, and tracing-on, for both the
plain and the speculative engine.
"""
import json

import jax
import numpy as np
import pytest

from repro import configs
from repro.launch import serve
from repro.obs import NOOP, Observability
from repro.obs import validate as obs_validate
from repro.obs.export import metrics_snapshot, to_prometheus
from repro.obs.metrics import (NOOP_INSTRUMENT, NOOP_REGISTRY, Histogram,
                               MetricsRegistry)
from repro.obs.schema import load_schema, validate
from repro.obs.trace import NOOP_TRACER, Tracer, request_tid
from repro.serve import Engine
from repro.spec import SpecEngine

ARCH = "qwen1.5-0.5b"
MIXED_LENS = [4, 7, 11, 16]
GEN = 5


@pytest.fixture(scope="module")
def loaded():
    cfg = configs.get_smoke(ARCH)
    params, qcfg = serve.load_quantized(cfg, jax.random.PRNGKey(0), "packed")
    return cfg, params, qcfg


def _prompts(cfg, lens, seed=3):
    rng = jax.random.PRNGKey(seed)
    return [np.asarray(jax.random.randint(jax.random.fold_in(rng, i),
                                          (l,), 4, cfg.vocab_size))
            for i, l in enumerate(lens)]


def _engine(cfg, params, qcfg, klass=Engine, **kw):
    kw.setdefault("n_slots", 4)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_blocks_per_slot", 4)
    kw.setdefault("n_blocks", 16)
    return klass(cfg, params, qcfg, **kw)


def _run(eng, prompts, gen=GEN):
    rids = [eng.submit(p, gen) for p in prompts[:2]]
    eng.step()                                      # staggered arrivals
    rids += [eng.submit(p, gen) for p in prompts[2:]]
    outputs = eng.drain(max_steps=500)
    return rids, outputs


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_histogram_reservoir_bounded_stats_exact():
    h = Histogram("t", cap=64)
    vals = [float(i) for i in range(10_000)]
    for v in vals:
        h.observe(v)
    assert h.count == 10_000
    assert h.sum == sum(vals)
    assert h.min == 0.0 and h.max == 9999.0
    assert len(h.reservoir) <= 64                   # bounded forever
    p50 = h.percentile(50)
    assert 0.0 <= p50 <= 9999.0
    # a uniform reservoir over a uniform stream: the median estimate
    # cannot collapse to either extreme decile
    assert 1000.0 < p50 < 9000.0


def test_histogram_percentiles_none_when_empty():
    h = Histogram("empty")
    assert h.percentile(50) is None
    snap = h.snapshot()
    assert snap["count"] == 0 and snap["p50"] is None and snap["min"] is None


def test_histogram_reservoir_deterministic():
    def fill(name):
        h = Histogram(name, cap=8)
        for i in range(1000):
            h.observe(float(i))
        return h.reservoir

    assert fill("a") == fill("a")                   # per-name seeded LCG
    assert fill("a") != fill("b")


def test_registry_counters_gauges_and_kind_conflict():
    m = MetricsRegistry()
    c = m.counter("reqs", "help", labels=("event",))
    c.labels(event="submitted").inc()
    c.labels(event="submitted").inc(2)
    g = m.gauge("depth")
    g.set(7)
    assert m.counter("reqs") is c                   # same name -> same object
    with pytest.raises(ValueError):
        m.gauge("reqs")                             # kind conflict
    snap = m.snapshot()
    assert snap["reqs"]["labels"][0]["value"] == 3.0
    assert snap["depth"]["value"] == 7.0
    # exported text parses per the CI validator's line grammar
    assert obs_validate.check_prometheus(m.to_prometheus()) == []


def test_noop_registry_is_true_noop():
    assert NOOP_REGISTRY.enabled is False
    c = NOOP_REGISTRY.counter("x", labels=("a",))
    assert c is NOOP_INSTRUMENT
    assert c.labels(a="y") is NOOP_INSTRUMENT       # no child allocation
    assert NOOP_REGISTRY.histogram("h") is NOOP_INSTRUMENT
    NOOP_INSTRUMENT.inc()
    NOOP_INSTRUMENT.observe(1.0)
    assert NOOP_INSTRUMENT.percentile(50) is None
    assert NOOP_REGISTRY.snapshot() == {}
    assert NOOP_REGISTRY.to_prometheus() == ""
    assert NOOP.enabled is False and NOOP.dispatch is None


# ---------------------------------------------------------------------------
# tracer + mini schema validator
# ---------------------------------------------------------------------------


def test_tracer_spans_nest_and_chrome_doc_validates():
    tr = Tracer()
    tr.thread_name(request_tid(0), "request 0")
    tr.begin("request", request_tid(0), rid=0)
    with tr.span("engine.decode_step"):
        with tr.span("spec.verify"):
            pass
    tr.instant("first_token", request_tid(0), token=5)
    tr.end("request", request_tid(0))
    doc = tr.to_chrome()
    assert validate(doc, load_schema("trace")) == []
    names = [e["name"] for e in doc["traceEvents"] if e["ph"] != "M"]
    assert names == ["request", "engine.decode_step", "spec.verify",
                     "spec.verify", "engine.decode_step", "first_token",
                     "request"]


def test_trace_validator_catches_bad_docs():
    tr = Tracer()
    tr.begin("request", 1)                          # never closed
    doc = tr.to_chrome()
    errs = obs_validate.check_trace(doc)
    assert any("unclosed" in e for e in errs)
    assert any("never occurs" in e for e in errs)   # missing lifecycle spans

    # schema-level: wrong ph enum
    doc2 = tr.to_chrome()
    doc2["traceEvents"][0]["ph"] = "X"
    assert validate(doc2, load_schema("trace")) != []


def test_noop_tracer_records_nothing():
    assert NOOP_TRACER.enabled is False
    NOOP_TRACER.begin("x")
    with NOOP_TRACER.span("y"):
        pass
    with NOOP_TRACER.annotate("z"):
        pass
    assert NOOP_TRACER.events == ()
    assert NOOP_TRACER.to_chrome()["traceEvents"] == []


def test_mini_schema_validator():
    schema = {"type": "object", "required": ["a"],
              "properties": {"a": {"type": "integer"},
                             "b": {"type": ["number", "null"]},
                             "c": {"enum": ["x", "y"]}},
              "additionalProperties": False}
    assert validate({"a": 1, "b": None, "c": "x"}, schema) == []
    assert validate({"a": 1, "b": 2.5}, schema) == []
    assert any("required" in e for e in validate({}, schema))
    assert validate({"a": "nope"}, schema) != []
    assert validate({"a": 1, "c": "z"}, schema) != []
    assert validate({"a": 1, "zz": 0}, schema) != []
    # bool is NOT an integer/number here (json-schema semantics)
    assert validate({"a": True}, schema) != []


# ---------------------------------------------------------------------------
# engine integration: parity, lifecycle, exports
# ---------------------------------------------------------------------------


def test_engine_tokens_bitwise_identical_with_obs_on(loaded):
    cfg, params, qcfg = loaded
    prompts = _prompts(cfg, MIXED_LENS)
    _, base = _run(_engine(cfg, params, qcfg), prompts)
    for obs in (Observability(metrics=True, trace=False),
                Observability(metrics=True, trace=True)):
        _, got = _run(_engine(cfg, params, qcfg, obs=obs), prompts)
        assert set(got) == set(base)
        for rid in base:
            np.testing.assert_array_equal(got[rid], base[rid])


def test_spec_engine_tokens_bitwise_identical_with_obs_on(loaded):
    cfg, params, qcfg = loaded
    prompts = _prompts(cfg, MIXED_LENS)
    kw = dict(klass=SpecEngine, draft_k=2)
    _, base = _run(_engine(cfg, params, qcfg, **kw), prompts)
    obs = Observability(metrics=True, trace=True)
    _, got = _run(_engine(cfg, params, qcfg, obs=obs, **kw), prompts)
    for rid in base:
        np.testing.assert_array_equal(got[rid], base[rid])


def test_engine_trace_lifecycle_and_schema(loaded):
    cfg, params, qcfg = loaded
    obs = Observability(metrics=True, trace=True)
    eng = _engine(cfg, params, qcfg, obs=obs)
    rids, _ = _run(eng, _prompts(cfg, MIXED_LENS))

    doc = obs.trace.to_chrome()
    assert obs_validate.check_trace(doc) == []      # schema + span semantics
    for rid in rids:
        lane = [e for e in doc["traceEvents"]
                if e["ph"] in "BEi" and e["tid"] == request_tid(rid)]
        order = [(e["ph"], e["name"]) for e in lane]
        # queue nests in request; prefill/first_token/decode follow in order
        assert order[0] == ("B", "request")
        assert order[1] == ("B", "queue")
        assert order[-1] == ("E", "request")
        assert ("i", "first_token") in order
        assert order.index(("E", "prefill")) < order.index(("i",
                                                            "first_token"))


def test_engine_metrics_snapshot_schema_and_prometheus(loaded):
    cfg, params, qcfg = loaded
    obs = Observability(metrics=True, trace=False)
    eng = _engine(cfg, params, qcfg, obs=obs)
    _run(eng, _prompts(cfg, MIXED_LENS))

    snap = metrics_snapshot(eng)
    assert obs_validate.check_metrics(snap) == []
    assert json.dumps(snap)                         # JSON-serializable
    assert snap["engine"]["kind"] == "engine"
    assert snap["speculative"]["enabled"] is False
    assert snap["latency"]["ttft_p50_s"] > 0.0
    assert snap["metrics"]["serve_ttft_seconds"]["count"] == len(MIXED_LENS)
    assert snap["metrics"]["serve_tokens_total"]["labels"]
    assert obs_validate.check_prometheus(
        to_prometheus(snap, eng.obs.metrics)) == []


def test_engine_dispatch_counters_packed(loaded):
    cfg, params, qcfg = loaded
    obs = Observability(metrics=True)
    eng = _engine(cfg, params, qcfg, obs=obs)
    _run(eng, _prompts(cfg, MIXED_LENS))

    snap = obs.metrics.snapshot()
    gemm = {e["labels"]["backend"]: e["value"]
            for e in snap["qeinsum_dispatch_total"]["labels"]}
    assert gemm.get("pallas_2d", 0) > 0             # packed 2-D GEMMs traced
    bts = {e["labels"]["backend"]: e["value"]
           for e in snap["qeinsum_weight_bytes_total"]["labels"]}
    assert bts["pallas_2d"] > 0                     # analytic bytes recorded
    kern = {e["labels"]["kernel"]: e["value"]
            for e in snap["kernel_dispatch_total"]["labels"]}
    assert kern.get("nvfp4_matmul", 0) > 0
    if eng.fused:
        assert kern.get("paged_attention", 0) > 0


def test_engine_stats_unified_keys_and_none_percentiles(loaded):
    cfg, params, qcfg = loaded
    eng = _engine(cfg, params, qcfg)
    st = eng.stats()                                # nothing served yet
    assert st["speculative"] is False
    assert st["acceptance_rate"] is None
    assert st["accepted_per_step"] is None
    assert st["ttft_p50_s"] is None                 # no data != 0.0
    assert st["decode_lat_p95_s"] is None

    _run(eng, _prompts(cfg, MIXED_LENS))
    st = eng.stats()
    assert st["ttft_p50_s"] > 0.0 and st["decode_lat_p95_s"] > 0.0


def test_spec_engine_trace_and_counters(loaded):
    cfg, params, qcfg = loaded
    obs = Observability(metrics=True, trace=True)
    eng = _engine(cfg, params, qcfg, klass=SpecEngine, draft_k=2, obs=obs)
    _run(eng, _prompts(cfg, MIXED_LENS))

    doc = obs.trace.to_chrome()
    assert obs_validate.check_trace(doc, expect_spec=True) == []
    st = eng.stats()
    assert st["speculative"] is True
    assert st["drafted_tokens"] > 0

    snap = obs.metrics.snapshot()
    drafted = {e["labels"]["draft"]: e["value"]
               for e in snap["spec_draft_tokens_total"]["labels"]}
    accepted = {e["labels"]["draft"]: e["value"]
                for e in snap["spec_accepted_tokens_total"]["labels"]}
    assert drafted["self-qdq"] == st["drafted_tokens"]   # counters == stats
    assert accepted["self-qdq"] == st["accepted_tokens"]
    assert snap["spec_draft_steps_total"]["value"] > 0
    assert snap["spec_verify_seconds"]["count"] == st["verify_steps"]

    spec_snap = metrics_snapshot(eng)
    assert obs_validate.check_metrics(spec_snap, expect_spec=True) == []
    assert spec_snap["engine"]["kind"] == "spec"


def test_engine_metrics_off_allocates_no_instruments(loaded):
    cfg, params, qcfg = loaded
    eng = _engine(cfg, params, qcfg)                # no obs bundle
    assert eng.obs is NOOP
    assert eng._m_ttft is NOOP_INSTRUMENT           # shared no-op handles
    assert eng._m_req_finished["eos"] is NOOP_INSTRUMENT
    _run(eng, _prompts(cfg, MIXED_LENS[:2]))
    assert eng.obs.metrics.snapshot() == {}
    assert eng.obs.trace.events == ()


def test_noop_tracer_allocates_nothing_per_call():
    """Every span of the disabled tracer is the one shared null object, and
    ten thousand of them (with args, as the engine passes them) leave no
    allocation behind."""
    import tracemalloc

    ctx = NOOP_TRACER.annotate("engine.decode_step", n_active=3)
    assert ctx is NOOP_TRACER.annotate("engine.decode.wait")
    assert ctx is NOOP_TRACER.span("engine.step")
    tracemalloc.start()
    try:
        before = tracemalloc.get_traced_memory()[0]
        for i in range(10_000):
            with NOOP_TRACER.annotate("engine.decode.emit", n_tokens=i):
                pass
        after = tracemalloc.get_traced_memory()[0]
    finally:
        tracemalloc.stop()
    assert after - before < 100            # the loop's last int, at most
    assert NOOP_TRACER.events == ()


def _engine_lane(doc):
    return [e for e in doc["traceEvents"]
            if e["tid"] == 0 and e["ph"] in "BE"]


def test_engine_span_tree_nests_inside_the_step(loaded):
    """On the engine lane every span opens and closes inside an
    ``engine.step``; each ``engine.decode_step`` holds its children in
    order, one wait among them, and its tokens' ``engine.decode.emit``
    follows it."""
    cfg, params, qcfg = loaded
    obs = Observability(metrics=False, trace=True)
    eng = _engine(cfg, params, qcfg, obs=obs)
    _run(eng, _prompts(cfg, MIXED_LENS))
    lane = _engine_lane(obs.trace.to_chrome())
    stack, children, n_steps = [], [], 0
    for e in lane:
        if e["ph"] == "B":
            if e["name"] != "engine.step":
                assert stack and stack[0] == "engine.step", e["name"]
            if e["name"].startswith("engine.decode."):
                assert stack[-1] == ("engine.step"
                                     if e["name"] == "engine.decode.emit"
                                     else "engine.decode_step"), e["name"]
                children.append(e["name"])
            if e["name"] == "engine.decode_step":
                n_steps += 1
            stack.append(e["name"])
        else:
            assert stack.pop() == e["name"]
    assert stack == []
    assert n_steps == eng.decode_steps > 0
    step = ["engine.decode.inputs", "engine.decode.dispatch",
            "engine.decode.sample", "engine.decode.wait",
            "engine.decode.emit"]
    assert children == step * n_steps
    admits = [e for e in lane if e["name"] == "engine.admit"
              and e["ph"] == "B"]
    firsts = [e for e in lane if e["name"] == "engine.first_token"
              and e["ph"] == "B"]
    assert len(admits) == len(firsts) == len(MIXED_LENS)
    assert {e["args"]["rid"] for e in admits} == set(range(len(MIXED_LENS)))


def test_token_stamps_give_the_inter_token_gaps(loaded):
    """Each emitted token is stamped on its request; the decode latency
    percentiles are those of the gaps between consecutive stamps."""
    cfg, params, qcfg = loaded
    eng = _engine(cfg, params, qcfg)
    _run(eng, _prompts(cfg, MIXED_LENS))
    gaps = []
    for r in eng.sched.finished.values():
        assert len(r.token_t) == len(r.output) == GEN
        assert r.token_t[0] == r.first_tok_t and r.token_t[-1] == r.last_tok_t
        assert r.token_t == sorted(r.token_t)
        gaps += list(np.diff(r.token_t))
    assert sorted(eng.token_gaps()) == sorted(gaps)
    st = eng.stats()
    assert st["decode_lat_p50_s"] == pytest.approx(np.percentile(gaps, 50))
    assert st["decode_lat_p95_s"] == pytest.approx(np.percentile(gaps, 95))


def test_compile_watch_counts_programs_without_gemms(loaded, capsys):
    """The watch counts a jitted call that traced, whatever it holds: the
    sampler has no GEMM.  A retrace past warm-up warns once."""
    from repro.obs import dispatch as obs_dispatch

    n = obs_dispatch.programs_traced()
    f = jax.jit(lambda x: x + 1)
    f(np.zeros(3, np.float32))
    assert obs_dispatch.programs_traced() > n
    n = obs_dispatch.programs_traced()
    f(np.ones(3, np.float32))                       # compiled: no trace
    assert obs_dispatch.programs_traced() == n

    cfg, params, qcfg = loaded
    # a slot count no other engine of this module uses: jit shares traced
    # programs across engines, so only a new shape traces here
    eng = _engine(cfg, params, qcfg, obs=Observability(metrics=True),
                  n_slots=3)
    _run(eng, _prompts(cfg, MIXED_LENS))
    comp = eng.obs.metrics.get("jit_compiles_total").snapshot()
    fns = {c["labels"]["fn"]: c["value"] for c in comp["labels"]}
    assert fns["decode"] >= 1 and fns["sample"] >= 1
    # a new specialization past warm-up: one count and one warning
    eng.decode_steps = 10
    eng._compile_watch("sample", lambda: jax.jit(lambda x: x * 2)(1.0))
    eng._compile_watch("sample", lambda: jax.jit(lambda x: x * 3)(1.0))
    comp = eng.obs.metrics.get("jit_compiles_total").snapshot()
    after = {c["labels"]["fn"]: c["value"] for c in comp["labels"]}
    assert after["sample"] == fns["sample"] + 2
    assert capsys.readouterr().err.count("recompiled") == 1
