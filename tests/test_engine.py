"""Continuous-batching engine (repro.serve): paged pool invariants,
scheduler admission/retirement, sampling, and the acceptance workload —
mixed prompt lengths (>= 4x spread), staggered arrivals, per-request greedy
outputs matching single-request static ``serve_batch`` token-for-token on
both qdq and packed weight formats.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.launch import serve
from repro.models import decoder
from repro.serve import Engine, PagedKVPool, SamplingParams, sample_tokens
from repro.serve.paged_kv import PoolExhausted

ARCH = "qwen1.5-0.5b"
# 8 requests, prompt lengths 4..16 (4x spread)
MIXED_LENS = [4, 6, 7, 9, 11, 13, 14, 16]
GEN = 5


@pytest.fixture(scope="module")
def loaded():
    cfg = configs.get_smoke(ARCH)
    rng = jax.random.PRNGKey(0)
    out = {}
    for fmt in ("qdq", "packed"):
        out[fmt] = serve.load_quantized(cfg, rng, fmt)
    return cfg, out


def _prompts(cfg, lens, seed=3):
    rng = jax.random.PRNGKey(seed)
    return [np.asarray(jax.random.randint(jax.random.fold_in(rng, i),
                                          (l,), 4, cfg.vocab_size))
            for i, l in enumerate(lens)]


def _engine(cfg, params, qcfg, **kw):
    kw.setdefault("n_slots", 4)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_blocks_per_slot", 4)
    kw.setdefault("n_blocks", 16)
    return Engine(cfg, params, qcfg, **kw)


# ---------------------------------------------------------------------------
# paged pool
# ---------------------------------------------------------------------------


def test_pool_alloc_free_invariants():
    cfg = configs.get_smoke(ARCH)
    pool = PagedKVPool(decoder.init_paged_pool(cfg, 8, 4), 4)
    assert pool.n_blocks == 8 and pool.free_blocks == 8 and not pool.fp8
    a = pool.alloc(3)
    b = pool.alloc(5)
    assert pool.free_blocks == 0 and pool.used_blocks == 8
    assert sorted(a + b) == list(range(8))          # disjoint, full coverage
    assert not pool.can_alloc(1)
    with pytest.raises(PoolExhausted):
        pool.alloc(1)
    pool.free(a)
    assert pool.free_blocks == 3
    with pytest.raises(ValueError):
        pool.free(a)                                # double free detected
    pool.free(b)
    assert pool.free_blocks == 8 and pool.used_blocks == 0
    assert pool.peak_used == 8
    assert pool.blocks_for(1) == 1 and pool.blocks_for(9) == 3


def test_pool_fp8_pages_carry_scales():
    cfg = dataclasses.replace(configs.get_smoke(ARCH),
                              quant_recipe="moe_hybrid")
    data = decoder.init_paged_pool(cfg, 4, 8)
    pool = PagedKVPool(data, 8)
    assert pool.fp8
    assert data["k"].dtype == jnp.float8_e4m3fn
    # pages [L, n_blocks, bs, Hkv*hd], one scale per (slot, head)
    assert data["k"].shape == (cfg.n_layers, 4, 8,
                               cfg.n_kv_heads * cfg.head_dim)
    assert data["k_scale"].shape == (*data["k"].shape[:-1], cfg.n_kv_heads)
    assert data["k_scale"].dtype == jnp.float32
    # pool bytes charge pages AND scales
    assert pool.nbytes() == sum(int(a.nbytes) for a in data.values())


# ---------------------------------------------------------------------------
# acceptance workload: mixed lengths, staggered arrivals, serve_batch parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fmt", ["qdq", "packed"])
def test_engine_mixed_workload_matches_serve_batch(loaded, fmt):
    cfg, by_fmt = loaded
    params, qcfg = by_fmt[fmt]
    eng = _engine(cfg, params, qcfg)
    prompts = _prompts(cfg, MIXED_LENS)

    rids = [eng.submit(p, GEN) for p in prompts[:4]]
    eng.step()                                      # first wave decoding...
    rids += [eng.submit(p, GEN) for p in prompts[4:]]   # ...late arrivals
    outputs = eng.drain(max_steps=500)

    assert len(outputs) == len(prompts)
    assert eng.pool.used_blocks == 0                # no block leaked
    for rid, prompt in zip(rids, prompts):
        ref, _ = serve.serve_batch(eng.cfg, params, jnp.asarray(prompt[None]),
                                   GEN, qcfg=qcfg)
        np.testing.assert_array_equal(outputs[rid], np.asarray(ref[0]),
                                      err_msg=f"request {rid} diverged")


def test_engine_fp8_kv_moe_matches_serve_batch():
    """FP8 paged pool + MoE (arctic smoke, moe_hybrid recipe): per-request
    parity holds and the pool pages carry scales."""
    cfg = configs.get_smoke("arctic-480b")
    rng = jax.random.PRNGKey(0)
    params, qcfg = serve.load_quantized(cfg, rng, "qdq")
    eng = _engine(cfg, params, qcfg, n_slots=2)
    assert eng.pool.fp8
    prompts = _prompts(cfg, [4, 9, 16], seed=5)
    rids = [eng.submit(p, 4) for p in prompts]
    outputs = eng.drain(max_steps=200)
    assert eng.pool.used_blocks == 0
    for rid, prompt in zip(rids, prompts):
        ref, _ = serve.serve_batch(eng.cfg, params, jnp.asarray(prompt[None]),
                                   4, qcfg=qcfg)
        np.testing.assert_array_equal(outputs[rid], np.asarray(ref[0]))


# ---------------------------------------------------------------------------
# scheduler: admission, capacity, retirement, backfill
# ---------------------------------------------------------------------------


def test_admission_refuses_when_pool_exhausted(loaded):
    cfg, by_fmt = loaded
    params, qcfg = by_fmt["qdq"]
    # pool holds exactly one request's worst case: 16 prompt + 5 gen
    eng = _engine(cfg, params, qcfg, n_blocks=3, n_slots=4)
    prompts = _prompts(cfg, [16, 16, 16], seed=7)
    rids = [eng.submit(p, GEN) for p in prompts]
    eng.step()
    # one admitted (3 blocks), the rest must wait on capacity despite slots
    assert len(eng.sched.in_flight()) == 1
    assert len(eng.sched.waiting) == 2
    assert eng.sched.admit_next() is None
    outputs = eng.drain(max_steps=500)              # serial completion
    assert sorted(outputs) == sorted(rids)
    assert eng.pool.used_blocks == 0
    assert eng.pool.peak_used == 3


def test_eos_retires_and_backfills(loaded):
    cfg, by_fmt = loaded
    params, qcfg = by_fmt["qdq"]
    prompts = _prompts(cfg, [8, 8, 8], seed=9)
    # reference first token of request 0 becomes the EOS id
    ref, _ = serve.serve_batch(cfg, params, jnp.asarray(prompts[0][None]),
                               GEN, qcfg=qcfg)
    eos = int(np.asarray(ref[0][0]))
    eng = _engine(cfg, params, qcfg, n_slots=1, eos_id=eos)
    rids = [eng.submit(p, GEN) for p in prompts]
    outputs = eng.drain(max_steps=500)
    r0 = eng.sched.finished[rids[0]]
    assert r0.finish_reason == "eos"
    assert outputs[rids[0]].tolist() == [eos]       # stopped at first token
    # the single slot was retired and backfilled until everyone completed
    assert sorted(outputs) == sorted(rids)
    assert all(eng.sched.finished[r].finish_reason in ("eos", "length")
               for r in rids)
    assert eng.pool.used_blocks == 0


def test_scheduler_rejects_oversized_request(loaded):
    cfg, by_fmt = loaded
    params, qcfg = by_fmt["qdq"]
    eng = _engine(cfg, params, qcfg)                # 4 blocks x 8 = 32 max
    with pytest.raises(ValueError, match="max_blocks_per_slot"):
        eng.submit(np.arange(4, 40, dtype=np.int32), 10)


def test_scheduler_rejects_never_admittable_vs_pool_capacity(loaded):
    """The never-admittable guard's POOL branch: a request within
    max_blocks_per_slot but needing more blocks than the whole pool owns
    must be refused at submit (it could never be admitted, only deadlock
    the FIFO head)."""
    cfg, by_fmt = loaded
    params, qcfg = by_fmt["qdq"]
    # per-slot cap is generous (16 blocks) but the pool only owns 3
    eng = _engine(cfg, params, qcfg, n_blocks=3, max_blocks_per_slot=16,
                  n_slots=2)
    with pytest.raises(ValueError, match="pool capacity"):
        eng.submit(np.arange(4, 36, dtype=np.int32), 10)   # needs 6 > 3
    # boundary: exactly the pool's capacity is admittable
    rid = eng.submit(np.arange(4, 24, dtype=np.int32), 5)  # needs 3 == 3
    outputs = eng.drain(max_steps=200)
    assert list(outputs) == [rid]
    assert eng.pool.used_blocks == 0


def test_head_of_line_giant_blocks_small_requests(loaded):
    """Documented FIFO semantics: the queue head waits for ITS reservation;
    later small requests do not bypass it even when they would fit now."""
    cfg, by_fmt = loaded
    params, qcfg = by_fmt["qdq"]
    eng = _engine(cfg, params, qcfg, n_blocks=4, n_slots=2)
    running = eng.submit(_prompts(cfg, [16], seed=15)[0], GEN)   # 3 blocks
    eng.step()                                      # running: 1 block free
    giant = eng.submit(_prompts(cfg, [16], seed=16)[0], GEN)     # needs 3
    small = eng.submit(_prompts(cfg, [4], seed=17)[0], 3)        # needs 1
    eng.step()
    in_flight = {r.rid for r in eng.sched.in_flight()}
    assert giant not in in_flight
    assert small not in in_flight                   # no small-request bypass
    assert [r.rid for r in eng.sched.waiting] == [giant, small]
    outputs = eng.drain(max_steps=500)              # everyone finishes FIFO
    assert sorted(outputs) == sorted([running, giant, small])
    assert eng.pool.used_blocks == 0


def test_engine_latency_telemetry(loaded):
    cfg, by_fmt = loaded
    params, qcfg = by_fmt["qdq"]
    eng = _engine(cfg, params, qcfg)
    rids = [eng.submit(p, 4) for p in _prompts(cfg, [4, 9], seed=19)]
    eng.drain(max_steps=200)
    st = eng.stats()
    for key in ("ttft_p50_s", "ttft_p95_s", "decode_lat_p50_s",
                "decode_lat_p95_s"):
        assert st[key] > 0.0
    assert st["ttft_p50_s"] <= st["ttft_p95_s"]
    assert st["decode_lat_p50_s"] <= st["decode_lat_p95_s"]
    for rid in rids:
        req = eng.sched.finished[rid]
        assert req.first_tok_t >= req.submit_t > 0
        assert req.ttft_s > 0


def test_engine_rejects_unsupported_state_plans():
    """RWKV6 / RG-LRU / Whisper now serve through the state protocol; the
    remaining refusal is a plan with an unimplemented kind (qwen2-vl's
    vision_prefix), named in a one-line capability error."""
    cfg = configs.get_smoke("qwen2-vl-2b")
    with pytest.raises(ValueError, match="vision_prefix"):
        Engine(cfg, params={}, qcfg=None)


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------


def test_sampling_greedy_topk_and_determinism():
    rng = jax.random.PRNGKey(0)
    logits = jax.random.normal(rng, (4, 64))
    keys = jnp.stack([jax.random.PRNGKey(i) for i in range(4)])
    zeros = jnp.zeros((4,), jnp.float32)
    greedy = sample_tokens(logits, zeros, jnp.zeros((4,), jnp.int32), keys)
    np.testing.assert_array_equal(np.asarray(greedy),
                                  np.asarray(jnp.argmax(logits, -1)))
    # top_k=1 at any temperature is greedy
    t1 = sample_tokens(logits, jnp.full((4,), 1.7), jnp.ones((4,), jnp.int32),
                       keys)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(greedy))
    # same keys -> same draws; mixed rows respect their own params
    a = sample_tokens(logits, jnp.full((4,), 0.9), jnp.full((4,), 8), keys)
    b = sample_tokens(logits, jnp.full((4,), 0.9), jnp.full((4,), 8), keys)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # top-k masks: draws stay inside each row's top-8 set
    top8 = np.asarray(jnp.argsort(logits, -1)[:, -8:])
    for i, tok in enumerate(np.asarray(a)):
        assert tok in top8[i]


def test_topk_ties_admit_exactly_k():
    """Ties at the k-th logit must not inflate the candidate set: ranking
    is by (-logit, token id), so exactly k survive and tied candidates win
    by lower token id (a threshold test admits every tied token)."""
    from repro.serve.sampling import topk_mask

    logits = jnp.asarray([[0.0, 2.0, 2.0, 1.0]], jnp.float32)
    # k=1 with a tie at the top: only token 1 (the lower id) survives
    masked = np.asarray(topk_mask(logits, jnp.asarray([1])))
    assert np.isfinite(masked[0]).sum() == 1 and np.isfinite(masked[0, 1])
    # k=2: both tied tokens survive, nothing else
    masked = np.asarray(topk_mask(logits, jnp.asarray([2])))
    assert np.isfinite(masked[0]).sum() == 2
    assert np.isfinite(masked[0, 1]) and np.isfinite(masked[0, 2])
    # k=3 with the tie above the threshold: token 3 joins
    masked = np.asarray(topk_mask(logits, jnp.asarray([3])))
    assert np.isfinite(masked[0]).sum() == 3 and not np.isfinite(masked[0, 0])
    # sampling at k=1 can only ever return the tie-broken winner
    keys = jnp.stack([jax.random.PRNGKey(i) for i in range(5)])
    toks = sample_tokens(jnp.tile(logits, (5, 1)), jnp.full((5,), 1.3),
                         jnp.ones((5,), jnp.int32), keys)
    np.testing.assert_array_equal(np.asarray(toks), np.ones((5,), np.int32))
    # all-tied row: top_k=0 (full vocab) still reaches every token
    masked = np.asarray(topk_mask(jnp.zeros((1, 4)), jnp.asarray([0])))
    assert np.isfinite(masked).all()


def test_engine_sampled_requests_complete_deterministically(loaded):
    cfg, by_fmt = loaded
    params, qcfg = by_fmt["qdq"]
    sp = SamplingParams(temperature=0.8, top_k=16, seed=123)

    def run():
        eng = _engine(cfg, params, qcfg, n_slots=2)
        rids = [eng.submit(p, 4, sampling=sp)
                for p in _prompts(cfg, [5, 12], seed=11)]
        return [eng.drain(max_steps=200)[r].tolist() for r in rids]

    first, second = run(), run()
    # per-request seeds -> identical streams across runs and schedules
    assert first == second


# ---------------------------------------------------------------------------
# chunked prefill
# ---------------------------------------------------------------------------


def test_chunked_prefill_logits_within_tolerance(loaded):
    """Chunked prefill accuracy vs exact whole-prompt prefill on a qdq
    model.  Chunking only changes the dynamic activation amaxes (they
    become chunk-granular), so the final-position logits must stay close:
    stated tolerance max|dlogit| <= 0.75 * logit scale, mean <= 0.25 *
    scale, correlation >= 0.8 (measured ~0.45 / ~0.11 / ~0.92 at smoke
    scale).  A chunk that covers the whole prompt derives the same amaxes
    and must be BITWISE identical."""
    import dataclasses as _dc

    cfg, by_fmt = loaded
    params, qcfg = by_fmt["qdq"]
    sq = _dc.replace(qcfg, quantize_weights=False, act_scope="row")
    from repro.models import common as mcommon

    p_len, bs = 16, 8
    prompt = _prompts(cfg, [p_len], seed=23)[0]
    ref, _ = decoder.prefill(cfg, params, {"tokens": jnp.asarray(prompt[None])},
                             sq, s_max=None)
    ref = np.asarray(ref[0, -1], np.float32)
    scale = float(np.abs(ref).max())

    def chunked(chunk):
        pool = decoder.init_paged_pool(cfg, 8, bs)
        scratch = mcommon.zeros_from_specs(
            decoder.prefill_scratch_specs(cfg, 32))
        bt = jnp.asarray(np.arange(4, dtype=np.int32))
        start, logits = 0, None
        while start < p_len:
            n_valid = min(chunk, p_len - start)
            toks = np.zeros((1, chunk), np.int32)
            toks[0, :n_valid] = prompt[start:start + n_valid]
            logits, scratch, pool = decoder.prefill_chunk_paged(
                cfg, params, scratch, pool, bt,
                jnp.asarray(start, jnp.int32), jnp.asarray(n_valid, jnp.int32),
                {"tokens": jnp.asarray(toks)}, sq)
            start += n_valid
        return np.asarray(logits[0, -1], np.float32)

    np.testing.assert_array_equal(chunked(p_len), ref)   # one chunk: exact
    for chunk in (4, 8):
        got = chunked(chunk)
        d = np.abs(got - ref)
        assert d.max() <= 0.75 * scale, (chunk, d.max(), scale)
        assert d.mean() <= 0.25 * scale, (chunk, d.mean(), scale)
        assert np.corrcoef(got, ref)[0, 1] >= 0.8


def test_chunked_prefill_mixed_workload_completes(loaded):
    """Chunked mode interleaves long prompts across steps; numerics are
    approximate vs whole-prompt prefill (chunk-granular dynamic activation
    scales), so this asserts the scheduling invariants, not token parity."""
    cfg, by_fmt = loaded
    params, qcfg = by_fmt["qdq"]
    eng = _engine(cfg, params, qcfg, prefill_mode="chunked", prefill_chunk=4,
                  prefill_budget=6)
    prompts = _prompts(cfg, [4, 9, 16, 13], seed=13)
    rids = [eng.submit(p, 4) for p in prompts]
    outputs = eng.drain(max_steps=500)
    assert sorted(outputs) == sorted(rids)
    assert all(len(outputs[r]) == 4 for r in rids)
    assert eng.pool.used_blocks == 0


@pytest.mark.parametrize("acts", ["nvfp4", "bf16"])
def test_engine_logits_match_teacher_forced_serve_batch(loaded, acts):
    """The engine's own logits (``keep_logits``) against ``serve_batch``
    teacher-forced along the engine's stream, as ``chip_smoke.py`` gates
    them: bitwise here, where both run the same arithmetic.  A lost KV
    page, an engine defect that batching invariance cannot see since
    every run shares it, moves them far past the smoke's tolerance."""
    cfg, by_fmt = loaded
    params, qcfg = by_fmt["packed"]
    qcfg = dataclasses.replace(qcfg,
                               quantize_activations=(acts == "nvfp4"))
    prompts = _prompts(cfg, [9, 14])

    def run(lose_page):
        eng = _engine(cfg, params, qcfg)
        rids = [eng.submit(p, GEN, keep_logits=True) for p in prompts]
        eng.step()                                  # prefill + first decode
        if lose_page:
            blk = eng.sched.running()[0].block_ids[0]
            eng.pool.data = jax.tree.map(lambda a: a.at[:, blk].set(0),
                                         eng.pool.data)
        out = eng.drain(max_steps=500)
        return [(out[r], eng.logits(r)) for r in rids]

    for lose_page in (False, True):
        gaps = []
        for prompt, (stream, got) in zip(prompts, run(lose_page)):
            assert got.shape == (GEN, cfg.vocab_size)
            _, st = serve.serve_batch(cfg, params, jnp.asarray(prompt[None]),
                                      GEN, qcfg=qcfg, forced=stream[None],
                                      s_max=32)
            gaps.append(serve.teacher_forced_gap(got, st["logits"][0]))
        if not lose_page:
            assert all(g["rel"] == 0.0 and not g["splits"] for g in gaps)
        else:
            assert gaps[0]["rows"][0] == 0.0      # prefill ran before
            assert max(g["rel"] for g in gaps) > 1.0, gaps
