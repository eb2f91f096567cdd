"""Serve-path benchmark: QDQ vs packed-NVFP4 bytes + tok/s, and the
continuous-batching engine.

Runs the real serving driver (prefill + greedy decode) at smoke scale in
both weight formats across a dense, a MoE, and a recurrent arch, prices
the full-scale joint memory win (packed 0.5625 B/param weights + the
recipe's FP8-vs-BF16 KV cache at decode_32k), serves a mixed-length
staggered workload through the ``repro.serve`` engine (qdq and packed,
with TTFT / per-token latency percentiles), prices the TP partition
(``sharded`` section: per-device packed-weight and KV-pool bytes at tp=2/8
via ``sharding.resolve_packed``), compares the per-layer state protocol's
backends (``state_protocol`` section: packed-engine tok/s and per-slot
serve-state bytes for a paged-KV decoder vs constant-size slab-state
recurrent archs), and sweeps speculative decoding
(``repro.spec``) over draft length k — acceptance rate, per-slot accepted
tokens, and tok/s vs the plain-engine baseline for a dense and a
MoE/FP8-KV arch plus a two-model draft and an adaptive-k row (chosen-k
distribution) — and A/Bs the fused serving-kernel tier (``kernels``
section: per-decode-step latency with ``--fused-kernels`` off vs on and
the analytic bytes each step stops moving: dense gather intermediates,
MoE dequant slabs) — recording everything to ``BENCH_serve.json`` (and
the harness CSV via ``emit``):

    PYTHONPATH=src python -m benchmarks.serve_bench [--arch qwen1.5-0.5b]

Also registered as the "serve" row group in ``benchmarks.run``.

On this CPU container the packed numbers go through the interpret-mode
Pallas kernel, so tok/s is a correctness-weighted smoke signal; the byte
accounting (0.5625 vs 2.0 B/param on quantized GEMMs, 1 B/elem FP8 KV) is
exact and is the quantity that bounds memory-bound TPU decode.
"""
from __future__ import annotations

import argparse
import json
import sys

sys.path.insert(0, "src")

import jax                                                  # noqa: E402
import numpy as np                                          # noqa: E402

from repro import configs                                   # noqa: E402
from repro.configs import SHAPES                            # noqa: E402
from repro.launch import serve, specs                       # noqa: E402

from .common import emit                                    # noqa: E402

# dense / MoE / recurrent coverage per the roadmap
SWEEP_ARCHS = ("qwen1.5-0.5b", "qwen2-moe-a2.7b", "rwkv6-3b")


def bench_format(cfg, weight_format: str, batch: int, prompt_len: int,
                 gen: int) -> dict:
    rng = jax.random.PRNGKey(0)
    params, _ = serve.load_quantized(cfg, rng, weight_format)
    prompts = jax.random.randint(rng, (batch, prompt_len), 4, cfg.vocab_size)
    toks, stats = serve.serve_batch(cfg, params, prompts, gen)
    wr = serve.weight_report(params)
    return {"weight_format": weight_format,
            "tokens_head": [int(t) for t in toks[0, :8]],
            "decode_tok_s": stats["decode_tok_s"],
            "e2e_tok_s": stats["e2e_tok_s"],
            "prefill_s": stats["prefill_s"],
            "total_weight_bytes": wr["total_bytes"],
            "q_weight_bytes": wr["q_bytes"],
            "q_params": wr["q_params"],
            "q_bytes_per_param": wr["q_bytes_per_param"]}


def arch_rows(arch: str, batch: int, prompt_len: int, gen: int) -> dict:
    cfg = configs.get_smoke(arch)
    rows = {"formats": {}}
    for fmt in ("qdq", "packed"):
        r = bench_format(cfg, fmt, batch, prompt_len, gen)
        rows["formats"][fmt] = r
        emit(f"serve/{arch}/{fmt}_decode",
             1e6 / max(r["decode_tok_s"], 1e-9),
             f"tok_s={r['decode_tok_s']:.1f};"
             f"q_bytes_per_param={r['q_bytes_per_param']:.4f}")
    q, p = rows["formats"]["qdq"], rows["formats"]["packed"]
    rows["tokens_match"] = q["tokens_head"] == p["tokens_head"]
    rows["weight_bytes_ratio"] = (p["total_weight_bytes"]
                                  / max(q["total_weight_bytes"], 1))
    # full-scale analytic pricing: packed weights + recipe KV vs all-BF16
    rows["memory_full_scale"] = specs.serve_memory_report(
        configs.get_config(arch), SHAPES["decode_32k"])
    return rows


def engine_rows(arch: str, requests: int, gen: int, slots: int) -> dict:
    """Mixed-length staggered workload through the continuous-batching
    engine, qdq and packed: tok/s, pool utilization, weight + KV bytes."""
    cfg = configs.get_smoke(arch)
    # the real CLI parser supplies every engine knob's default; parity is
    # asserted by tests + CI, not re-run here
    args = serve.build_parser().parse_args(
        ["--engine", "--arch", arch, "--requests", str(requests),
         "--gen", str(gen), "--slots", str(slots), "--no-parity"])
    out = {"arch": arch, "requests": requests, "min_prompt": args.min_prompt,
           "max_prompt": args.max_prompt, "gen": gen, "slots": slots,
           "formats": {}}
    for fmt in ("qdq", "packed"):
        rng = jax.random.PRNGKey(0)
        params, qcfg = serve.load_quantized(cfg, rng, fmt)
        res = serve.run_engine(cfg, params, qcfg, args)
        st, wr = res["stats"], serve.weight_report(params)
        out["formats"][fmt] = {
            "completed": res["ok"], "pool_drained": res["pool_drained"],
            "decode_tok_s": st["decode_tok_s"], "e2e_tok_s": st["e2e_tok_s"],
            "steps": st["steps"], "peak_pool_utilization":
            st["peak_utilization"], "kv_pool_bytes": st["pool_bytes"],
            "weight_bytes": wr["total_bytes"],
            "serving_bytes": wr["total_bytes"] + st["pool_bytes"],
            "ttft_p50_s": st["ttft_p50_s"], "ttft_p95_s": st["ttft_p95_s"],
            "decode_lat_p50_s": st["decode_lat_p50_s"],
            "decode_lat_p95_s": st["decode_lat_p95_s"]}
        emit(f"serve/engine/{arch}/{fmt}",
             1e6 / max(st["decode_tok_s"], 1e-9),
             f"tok_s={st['decode_tok_s']:.1f};"
             f"pool_util={st['peak_utilization']:.2f}")
    return out


def state_protocol_rows(paged_arch: str,
                        slab_archs=("rwkv6-3b", "recurrentgemma-2b"),
                        requests: int = 4, gen: int = 6,
                        slots: int = 2) -> dict:
    """Per-layer state-protocol comparison: packed-weight engine tok/s and
    per-slot serve-state bytes for a paged-KV decoder vs the constant-size
    slab-state recurrent archs (growing block tables vs O(1) slabs)."""
    out = {}
    for a in dict.fromkeys((paged_arch, *slab_archs)):
        cfg = configs.get_smoke(a)
        args = serve.build_parser().parse_args(
            ["--engine", "--arch", a, "--requests", str(requests),
             "--gen", str(gen), "--slots", str(slots), "--no-parity"])
        params, qcfg = serve.load_quantized(cfg, jax.random.PRNGKey(0),
                                            "packed")
        res = serve.run_engine(cfg, params, qcfg, args)
        st = res["stats"]
        sp = specs.serve_memory_report(cfg)["state_protocol"]
        out[a] = {"plan": sp["plan"],
                  "state_backend": st["state_backend"],
                  "completed": res["ok"],
                  "state_drained": res["pool_drained"],
                  "decode_tok_s": st["decode_tok_s"],
                  "state_bytes_per_slot": sp["state_bytes_per_slot"],
                  "state_pool_bytes": st["pool_bytes"]}
        emit(f"serve/state/{a}", 1e6 / max(st["decode_tok_s"], 1e-9),
             f"plan={'+'.join(sp['plan'])};"
             f"bytes_per_slot={sp['state_bytes_per_slot']}")
    return out


def speculative_rows(dense_arch: str, moe_arch: str, gen: int,
                     ks=(2, 4)) -> dict:
    """Speculative decoding on the engine: acceptance rate, per-slot-round
    accepted tokens, and tok/s vs draft length k, for a dense (packed) and
    a MoE/FP8-KV (qdq) arch, plus a two-model draft row and a draft-cost-
    aware adaptive-k row (chosen-k distribution).  ``k0`` rows are the
    plain-engine baseline the speedup is measured against."""

    def one(arch, k, draft, adaptive=False):
        cfg = configs.get_smoke(arch)
        argv = ["--engine", "--arch", arch, "--requests", "4", "--gen",
                str(gen), "--slots", "2", "--no-parity"]
        if k:
            argv += ["--speculative", str(k), "--draft", draft]
        if adaptive:
            argv += ["--adaptive-k"]
        args = serve.build_parser().parse_args(argv)
        fmt = "qdq" if cfg.n_experts else "packed"
        params, qcfg = serve.load_quantized(cfg, jax.random.PRNGKey(0), fmt)
        res = serve.run_engine(cfg, params, qcfg, args)
        st = res["stats"]
        row = {"arch": arch, "k": k, "draft": draft if k else None,
               "weight_format": fmt, "completed": res["ok"],
               "pool_drained": res["pool_drained"],
               "decode_tok_s": st["decode_tok_s"],
               "e2e_tok_s": st["e2e_tok_s"],
               "ttft_p50_s": st["ttft_p50_s"],
               "decode_lat_p50_s": st["decode_lat_p50_s"]}
        if k:
            row.update({"acceptance_rate": st["acceptance_rate"],
                        "accepted_per_step": st["accepted_per_step"],
                        "rolled_back_tokens": st["rolled_back_tokens"],
                        "draft_pool_bytes": st["draft_pool_bytes"],
                        "adaptive_k": st["adaptive_k"],
                        "chosen_k_hist": st["chosen_k_hist"]})
            emit(f"serve/spec/{arch}/{draft}/k{k}"
                 + ("/adaptive" if adaptive else ""),
                 1e6 / max(st["decode_tok_s"], 1e-9),
                 f"acceptance={st['acceptance_rate']:.3f};"
                 f"accepted_per_step={st['accepted_per_step']:.2f}")
        return row

    out = {"dense": [one(dense_arch, 0, "self-qdq")],
           "moe": [one(moe_arch, 0, "self-qdq")]}
    for k in ks:
        out["dense"].append(one(dense_arch, k, "self-qdq"))
    out["moe"].append(one(moe_arch, ks[0], "self-qdq"))
    out["two_model"] = [one(dense_arch, ks[0], "two-model")]
    out["adaptive"] = [one(dense_arch, ks[-1], "self-qdq", adaptive=True)]
    return out


def _fusion_bytes_estimate(cfg, slots: int, s_alloc: int) -> dict:
    """Per-decode-step HBM traffic the fused tier removes (analytic).

    * attention: the gather+dequant two-step materializes a dense
      [slots, s_alloc, Hkv, hd] BF16 k AND v view per attention layer
      (write + re-read); the fused kernel streams pool pages straight into
      VMEM scratch.
    * MoE GEMMs: the dequant backend writes every expert's BF16 slab to
      HBM each step (then reads it back); the grouped kernel reads the
      packed 0.5625 B/param codes+scales only.
    """
    qcfg = specs.recipe_qconfig(cfg)
    kv_bytes = 1 if qcfg.kv_cache_dtype == "fp8" else 2
    hkv, hd = cfg.n_kv_heads, cfg.head_dim
    # dense BF16 intermediate (2 for k+v, 2 B/elem, write + re-read)
    gather = 2 * slots * s_alloc * hkv * hd * 2 * 2
    out = {"kv_elem_bytes": kv_bytes,
           "attn_layers": cfg.n_layers,
           "attn_gather_bytes_per_layer": gather,
           "attn_gather_bytes_per_step": gather * cfg.n_layers}
    if cfg.n_experts:
        # swiglu expert FFN: gate + up + down projections per expert
        params = cfg.n_experts * 3 * cfg.d_model * cfg.moe_d_ff
        out.update({
            "moe_layers": cfg.n_layers,
            "moe_expert_params_per_layer": params,
            "moe_dequant_slab_bytes_per_layer": params * 2 * 2,  # write+read
            "moe_packed_read_bytes_per_layer": int(params * 0.5625),
            "moe_dequant_slab_bytes_per_step": params * 2 * 2 * cfg.n_layers,
        })
    return out


def kernel_rows(dense_arch: str = "qwen1.5-0.5b",
                moe_arch: str = "arctic-480b", requests: int = 4,
                gen: int = 6, slots: int = 2) -> dict:
    """Fused serving-kernel tier A/B: the SAME packed engine workload with
    ``--fused-kernels`` off vs on (one-pass paged attention + grouped MoE
    GEMM), per-decode-step latency, and the analytic bytes-moved estimate
    for what fusion removes from each step.  Dense + MoE/FP8-KV archs."""
    out = {}
    for arch in dict.fromkeys((dense_arch, moe_arch)):
        cfg = configs.get_smoke(arch)
        params, qcfg = serve.load_quantized(cfg, jax.random.PRNGKey(0),
                                            "packed")
        row = {"arch": arch, "weight_format": "packed", "modes": {}}
        for mode in ("off", "on"):
            args = serve.build_parser().parse_args(
                ["--engine", "--arch", arch, "--requests", str(requests),
                 "--gen", str(gen), "--slots", str(slots), "--no-parity",
                 "--fused-kernels", mode])
            res = serve.run_engine(cfg, params, qcfg, args)
            st = res["stats"]
            row["modes"][mode] = {
                "completed": res["ok"],
                "fused_kernels": st["fused_kernels"],
                "packed_backend": st["packed_backend"],
                "decode_tok_s": st["decode_tok_s"],
                "decode_step_s": st["decode_s"] / max(st["decode_steps"], 1),
                "decode_lat_p50_s": st["decode_lat_p50_s"],
                "decode_lat_p95_s": st["decode_lat_p95_s"]}
            emit(f"serve/kernels/{arch}/fused_{mode}",
                 1e6 * row["modes"][mode]["decode_step_s"],
                 f"tok_s={st['decode_tok_s']:.1f};"
                 f"backend={st['packed_backend']}")
        on, off = row["modes"]["on"], row["modes"]["off"]
        row["decode_step_speedup"] = (off["decode_step_s"]
                                      / max(on["decode_step_s"], 1e-9))
        mb = max(1, -(-(args.max_prompt + gen - 1) // args.block_size))
        row["bytes_moved"] = _fusion_bytes_estimate(
            cfg, slots, mb * args.block_size)
        out[arch] = row
    return out


def _timed_step(eng, lats: list) -> None:
    """One ``eng.step()``; appends the wall time of its batched decode
    (``Engine.decode_s`` moves by it) to ``lats`` when it decoded."""
    d0, n0 = eng.decode_s, eng.decode_steps
    eng.step()
    if eng.decode_steps > n0:
        lats.append(eng.decode_s - d0)


def observability_rows(arch: str, requests: int, gen: int,
                       slots: int) -> dict:
    """Telemetry overhead A/B/C: the SAME packed engine workload with
    observability off / metrics / trace (``repro.obs``).  Per-step
    telemetry is a handful of bound-method calls in host Python between
    compiled steps — microseconds against a multi-ms decode step — so the
    measurement has to beat two CPU-container artifacts that each dwarf
    it: jit compile (each fresh engine's first drain; dominates mean
    tok/s at smoke-scale gen) and slow machine drift (~10% step-time
    wander over the minutes a sequential A/B/C takes — either sign,
    either order).  So: build all three engines up front, absorb compile
    in one untimed warmup drain per engine, then run the measured rounds
    with the three engines stepped in LOCKSTEP (per-step interleave) so
    drift lands on every mode within one step of itself, and
    compare the per-token latency FLOOR (min over steps — scheduling
    jitter only ever inflates a step, so the floor is where a systematic
    per-step cost would still show).  The acceptance bound is metrics-on
    overhead < 2% on the floor; decode tok/s and p50 are recorded
    per-mode as steady-state (post-warmup) context."""
    cfg = configs.get_smoke(arch)
    params, qcfg = serve.load_quantized(cfg, jax.random.PRNGKey(0),
                                        "packed")
    gen = max(gen, 12)                  # enough decode steps for the floor
    modes = ("off", "metrics", "trace")
    engines, baselines = {}, {}
    lats = {mode: [] for mode in modes}
    prompts = None
    for mode in modes:
        args = serve.build_parser().parse_args(
            ["--engine", "--arch", arch, "--requests", str(requests),
             "--gen", str(gen), "--slots", str(slots), "--no-parity",
             "--obs", mode])
        eng, _ = serve.build_engine(cfg, params, qcfg, args)
        engines[mode] = eng
        prompts = [np.asarray(p) for p in serve.mixed_prompts(
            jax.random.PRNGKey(7), requests, args.min_prompt,
            args.max_prompt, cfg.vocab_size)]
        for p in prompts:                            # warmup: compile lands
            eng.submit(p, gen)                       # on no mode's clock
        eng.drain(max_steps=2000)
        baselines[mode] = (eng.decode_s, eng.decode_tokens)
    for _ in range(3):                               # measured rounds
        for mode in modes:
            for p in prompts:
                engines[mode].submit(p, gen)
        # per-STEP interleave: the three engines advance in lockstep, so
        # machine drift lands on every mode within one ~step of itself
        while any(engines[m].sched.has_work() for m in modes):
            for mode in modes:
                if engines[mode].sched.has_work():
                    _timed_step(engines[mode], lats[mode])
    row = {"arch": arch, "weight_format": "packed", "gen": gen, "modes": {}}
    for mode in modes:
        eng = engines[mode]
        d0, t0 = baselines[mode]
        lat_min = min(lats[mode])
        row["modes"][mode] = {
            "completed": len(eng.outputs()) == 4 * requests,
            "decode_tok_s": ((eng.decode_tokens - t0)
                             / max(eng.decode_s - d0, 1e-9)),
            "decode_lat_p50_s": float(np.percentile(lats[mode], 50)),
            "decode_lat_min_s": lat_min}
        emit(f"serve/obs/{arch}/{mode}", lat_min * 1e6,
             f"tok_lat_min={lat_min * 1e3:.2f}ms")
    off = row["modes"]["off"]["decode_lat_min_s"]
    for m in ("metrics", "trace"):
        row[f"{m}_overhead_pct"] = 100.0 * (
            row["modes"][m]["decode_lat_min_s"] / max(off, 1e-9) - 1.0)
    return row


def numerics_rows(arch: str, requests: int, gen: int, slots: int) -> dict:
    """Numerics observability plane A/B: the SAME packed workload with the
    shadow teacher off / sampling 1-in-16 decode steps / sampling every
    step.  Reports the per-layer SQNR summary and live teacher-student KL
    at each rate, plus the probe overhead on the per-token decode-latency
    FLOOR (same lockstep + floor method as ``observability_rows``; the
    shadow forward itself runs between decode steps and is priced
    separately as ``shadow_s_per_sampled_step``).  Acceptance bound:
    sampled-probe overhead on the decode floor < 2%."""
    cfg = configs.get_smoke(arch)
    params, qcfg = serve.load_quantized(cfg, jax.random.PRNGKey(0),
                                        "packed")
    gen = max(gen, 12)
    rates = {"off": 0.0, "rate_1_16": 1.0 / 16.0, "rate_1": 1.0}
    engines = {}
    lats = {mode: [] for mode in rates}
    prompts = None
    for mode, rate in rates.items():
        argv = ["--engine", "--arch", arch, "--requests", str(requests),
                "--gen", str(gen), "--slots", str(slots), "--no-parity"]
        if rate:
            argv += ["--shadow-rate", str(rate)]
        args = serve.build_parser().parse_args(argv)
        eng, _ = serve.build_engine(cfg, params, qcfg, args)
        engines[mode] = eng
        prompts = [np.asarray(p) for p in serve.mixed_prompts(
            jax.random.PRNGKey(7), requests, args.min_prompt,
            args.max_prompt, cfg.vocab_size)]
        for p in prompts:                    # warmup: compile off the clock
            eng.submit(p, gen)
        eng.drain(max_steps=2000)
    for _ in range(3):                       # measured lockstep rounds
        for mode in rates:
            for p in prompts:
                engines[mode].submit(p, gen)
        while any(engines[m].sched.has_work() for m in rates):
            for mode in rates:
                if engines[mode].sched.has_work():
                    _timed_step(engines[mode], lats[mode])
    row = {"arch": arch, "weight_format": "packed", "gen": gen, "modes": {}}
    for mode, rate in rates.items():
        eng = engines[mode]
        r = {"shadow_rate": rate,
             "decode_lat_min_s": min(lats[mode]),
             "decode_lat_p50_s": float(np.percentile(lats[mode], 50))}
        if eng.numerics is not None:
            ns = eng.numerics.summary()
            kl = [v for _, v in ns["series"].get("qad_live_kl", [])]
            r.update({"shadow_steps": eng.shadow_steps,
                      "sampled_records": ns["sampled_records"],
                      "qad_live_kl_mean": (float(np.mean(kl)) if kl
                                           else None),
                      "qad_top1_agree_mean": (float(np.mean(
                          [v for _, v in ns["series"].get(
                              "qad_top1_agree", [])])) if kl else None),
                      "sqnr_db_min": ns["sqnr_db_min"],
                      "sqnr_db_mean": ns["sqnr_db_mean"]})
        row["modes"][mode] = r
        emit(f"serve/numerics/{arch}/{mode}", r["decode_lat_min_s"] * 1e6,
             f"tok_lat_min={r['decode_lat_min_s'] * 1e3:.2f}ms")
    off = row["modes"]["off"]["decode_lat_min_s"]
    row["probe_overhead_pct"] = 100.0 * (
        row["modes"]["rate_1_16"]["decode_lat_min_s"] / max(off, 1e-9) - 1.0)
    # price the sampled work itself: amortized shadow seconds per sampled
    # decode step at rate 1 (full-context teacher+student re-forwards)
    e1 = engines["rate_1"]
    row["shadow_s_per_sampled_step"] = (e1.shadow_s / e1.shadow_steps
                                        if e1.shadow_steps else None)
    # per-layer summary at rate 1 (densest sampling) for the artifact
    if e1.numerics is not None:
        row["per_layer"] = e1.numerics.summary()["per_layer"]
    return row


def _bursty_traffic(cfg, n: int, bs: int, seed=11):
    """Production-shaped request mix: 80% of prompts share a two-block
    (2*bs-token) head, lengths vary, and a third of the requests finish
    early (small token budget — the early-EOS population whose blocks the
    cache inherits).  Returns (prompts, per-request token budgets)."""
    rng = jax.random.PRNGKey(seed)
    head = np.asarray(jax.random.randint(jax.random.fold_in(rng, 0),
                                         (2 * bs,), 4, cfg.vocab_size),
                      np.int32)
    prompts, gens = [], []
    for i in range(n):
        tail = np.asarray(jax.random.randint(jax.random.fold_in(rng, i + 1),
                                             (2 + i % 6,), 4,
                                             cfg.vocab_size), np.int32)
        prompts.append(tail if i % 5 == 4                  # 20% unshared
                       else np.concatenate([head, tail]))
        gens.append(4 if i % 3 == 0 else 12)               # early-EOS third
    return prompts, gens


def prefix_cache_rows(arch: str = "qwen1.5-0.5b", n_requests: int = 12,
                      slots: int = 6, bs: int = 8,
                      n_blocks: int = 10) -> dict:
    """Heavy-traffic A/B for the serving-memory tentpole: the SAME bursty,
    80%-shared-prefix, early-EOS workload through (a) worst-case
    reservation with the cache off and (b) content-hashed prefix caching
    with on-demand paging + preemption, at the SAME pool size.  Records
    sustained tok/s, admission latency (queue wait), cache hit rate,
    preemption count, and the peak number of concurrently admitted
    requests — the capacity claim is ondemand/reserve concurrency >= 1.5x
    (or lower admission latency).  Outputs must match bitwise."""
    import time

    from repro.serve import Engine

    cfg = configs.get_smoke(arch)
    params, qcfg = serve.load_quantized(cfg, jax.random.PRNGKey(0),
                                        "packed")
    prompts, gens = _bursty_traffic(cfg, n_requests, bs)
    mb = max(1, -(-(max(len(p) for p in prompts) + max(gens) - 1) // bs))
    modes = {
        "reserve_cache_off": dict(prefix_cache=False, kv_alloc="reserve"),
        "ondemand_cache_on": dict(prefix_cache=True, kv_alloc="ondemand",
                                  headroom=1),
    }
    row = {"arch": arch, "weight_format": "packed",
           "requests": n_requests, "slots": slots, "block_size": bs,
           "n_blocks": n_blocks, "gens": gens, "modes": {}}
    outs_by_mode = {}
    for mode, kw in modes.items():
        eng = Engine(cfg, params, qcfg, n_slots=slots, block_size=bs,
                     n_blocks=n_blocks, max_blocks_per_slot=mb,
                     prefill_mode="paged", **kw)
        # bursty arrivals: waves of 4 with a couple of engine steps between
        rids, peak = [], 0
        t0 = time.time()
        for i, (p, g) in enumerate(zip(prompts, gens)):
            rids.append(eng.submit(p, g))
            if i % 4 == 3:
                for _ in range(2):
                    eng.step()
                    peak = max(peak, len(eng.sched.in_flight()))
        while eng.sched.has_work():
            eng.step()
            peak = max(peak, len(eng.sched.in_flight()))
        wall = time.time() - t0
        outs = eng.outputs()
        outs_by_mode[mode] = [outs[r] for r in rids]
        st = eng.stats()
        finished = list(eng.sched.finished.values())
        qwaits = [r.queue_wait_s for r in finished]
        cst = (eng.state.cache.stats() if eng.state.cache is not None
               else {})
        looked = cst.get("hits", 0) + cst.get("misses", 0)
        row["modes"][mode] = {
            "completed": len(outs) == n_requests,
            "pool_drained": not eng.state.leaked(),
            "sustained_tok_s": sum(len(o) for o in outs_by_mode[mode])
            / max(wall, 1e-9),
            "decode_tok_s": st["decode_tok_s"],
            "peak_concurrent": peak,
            "queue_wait_p50_s": float(np.percentile(qwaits, 50)),
            "queue_wait_mean_s": float(np.mean(qwaits)),
            "ttft_p50_s": st["ttft_p50_s"],
            "preempts": st.get("preempts", 0),
            "peak_pool_utilization": st["peak_utilization"],
            "cache_hits": cst.get("hits", 0),
            "cache_misses": cst.get("misses", 0),
            "cache_evictions": cst.get("evictions", 0),
            "cache_hit_rate": (cst.get("hits", 0) / looked if looked
                               else None),
        }
        emit(f"serve/prefix_cache/{arch}/{mode}",
             1e6 / max(row['modes'][mode]['sustained_tok_s'], 1e-9),
             f"tok_s={row['modes'][mode]['sustained_tok_s']:.1f};"
             f"peak_concurrent={peak}")
    a, b = outs_by_mode["reserve_cache_off"], outs_by_mode["ondemand_cache_on"]
    row["tokens_match_cache_off"] = all(
        np.array_equal(x, y) for x, y in zip(a, b))
    off, on = row["modes"]["reserve_cache_off"], row["modes"]["ondemand_cache_on"]
    row["concurrency_ratio"] = on["peak_concurrent"] \
        / max(off["peak_concurrent"], 1)
    row["queue_wait_ratio"] = on["queue_wait_mean_s"] \
        / max(off["queue_wait_mean_s"], 1e-9)
    return row


def sharded_rows(archs, tps=(2, 8), n_blocks: int = 1024) -> dict:
    """Per-device weight/KV bytes under TP partitions of the full-scale
    configs (analytic — ``sharding.resolve_packed`` divisibility, no
    devices needed): what each chip holds when ``PackedNVFP4`` codes/scales
    shard column-/row-parallel and the paged pool shards by KV heads."""
    out = {}
    for a in archs:
        cfg = configs.get_config(a)
        if cfg.family != "decoder":
            continue                    # paged TP serving is decoder-only
        out[a] = {}
        for tp in tps:
            rep = specs.serve_memory_report(cfg, SHAPES["decode_32k"],
                                            n_blocks=n_blocks, tp=tp)
            sh = rep.get("sharded")
            if not sh:
                continue
            sh["weight_shard_efficiency"] = (
                rep["weight_bytes_packed"]
                / max(sh["weight_bytes_packed_per_device"] * tp, 1))
            out[a][f"tp{tp}"] = sh
    return out


def serve_rows(arch="qwen1.5-0.5b", batch=4, prompt_len=16, gen=8,
               out="BENCH_serve.json", archs=SWEEP_ARCHS,
               engine_requests=6, engine_slots=3) -> dict:
    results = {"arch": arch, "batch": batch, "prompt_len": prompt_len,
               "gen": gen, "archs": {}}
    for a in dict.fromkeys((arch, *archs)):
        results["archs"][a] = arch_rows(a, batch, prompt_len, gen)
        m = results["archs"][a]["memory_full_scale"]
        joint = (f" joint(pkd+kv)={m['joint_ratio']:.3f}"
                 if "joint_ratio" in m else "")
        print(f"[serve_bench] {a}: tokens_match="
              f"{results['archs'][a]['tokens_match']} packed/qdq bytes="
              f"{results['archs'][a]['weight_bytes_ratio']:.3f}{joint}")
    # legacy top-level keys for the primary arch
    results.update({k: results["archs"][arch][k]
                    for k in ("formats", "tokens_match",
                              "weight_bytes_ratio")})

    results["engine"] = engine_rows(arch, engine_requests, gen,
                                    engine_slots)
    e = results["engine"]["formats"]
    print(f"[serve_bench] engine({arch}): "
          f"qdq={e['qdq']['decode_tok_s']:.1f} tok/s "
          f"packed={e['packed']['decode_tok_s']:.1f} tok/s "
          f"peak-pool-util={e['packed']['peak_pool_utilization']:.2f}")

    results["state_protocol"] = state_protocol_rows(arch, gen=gen)
    for a, row in results["state_protocol"].items():
        print(f"[serve_bench] state {a} ({'+'.join(row['plan'])}): "
              f"{row['decode_tok_s']:.1f} tok/s "
              f"{row['state_bytes_per_slot']}B/slot "
              f"drained={row['state_drained']}")

    results["sharded"] = sharded_rows(dict.fromkeys((arch, *archs)))
    for a, by_tp in results["sharded"].items():
        for tpname, sh in by_tp.items():
            print(f"[serve_bench] sharded {a} {tpname}: "
                  f"weights/dev={sh['weight_bytes_packed_per_device']/2**20:.1f}MiB "
                  f"kv-pool/dev={sh['kv_pool_bytes_per_device']/2**20:.1f}MiB "
                  f"shard-eff={sh['weight_shard_efficiency']:.3f}")

    results["kernels"] = kernel_rows(arch, gen=gen)
    for a, row in results["kernels"].items():
        bm = row["bytes_moved"]
        moe = (f" moe-dequant-avoided="
               f"{bm['moe_dequant_slab_bytes_per_step']/2**20:.2f}MiB/step"
               if "moe_dequant_slab_bytes_per_step" in bm else "")
        print(f"[serve_bench] kernels {a}: "
              f"step_off={row['modes']['off']['decode_step_s']*1e3:.1f}ms "
              f"step_on={row['modes']['on']['decode_step_s']*1e3:.1f}ms "
              f"speedup={row['decode_step_speedup']:.2f}x "
              f"gather-avoided="
              f"{bm['attn_gather_bytes_per_step']/2**20:.2f}MiB/step{moe}")

    results["observability"] = observability_rows(arch, engine_requests,
                                                  gen, engine_slots)
    ob = results["observability"]
    print(f"[serve_bench] observability {arch}: tok_lat_min "
          f"off={ob['modes']['off']['decode_lat_min_s'] * 1e3:.2f}ms "
          f"metrics={ob['modes']['metrics']['decode_lat_min_s'] * 1e3:.2f}ms "
          f"trace={ob['modes']['trace']['decode_lat_min_s'] * 1e3:.2f}ms "
          f"metrics-overhead={ob['metrics_overhead_pct']:+.1f}% "
          f"trace-overhead={ob['trace_overhead_pct']:+.1f}%")

    results["numerics"] = numerics_rows(arch, engine_requests, gen,
                                        engine_slots)
    nr = results["numerics"]
    r1 = nr["modes"]["rate_1"]
    print(f"[serve_bench] numerics {arch}: tok_lat_min "
          f"off={nr['modes']['off']['decode_lat_min_s'] * 1e3:.2f}ms "
          f"1/16={nr['modes']['rate_1_16']['decode_lat_min_s'] * 1e3:.2f}ms "
          f"1/1={r1['decode_lat_min_s'] * 1e3:.2f}ms "
          f"probe-overhead={nr['probe_overhead_pct']:+.1f}% "
          f"live_kl={r1['qad_live_kl_mean']:.4f} "
          f"sqnr_min={r1['sqnr_db_min']:.1f}dB")

    results["prefix_cache"] = prefix_cache_rows(arch)
    pc = results["prefix_cache"]
    on = pc["modes"]["ondemand_cache_on"]
    hr = on["cache_hit_rate"]
    print(f"[serve_bench] prefix_cache {arch}: "
          f"concurrency={pc['concurrency_ratio']:.2f}x "
          f"(peak {on['peak_concurrent']} vs "
          f"{pc['modes']['reserve_cache_off']['peak_concurrent']}) "
          f"queue-wait={pc['queue_wait_ratio']:.2f}x "
          f"hit-rate={f'{hr:.2f}' if hr is not None else 'n/a'} "
          f"preempts={on['preempts']} "
          f"tokens-match={pc['tokens_match_cache_off']}")

    results["speculative"] = speculative_rows(arch, "arctic-480b", gen)
    for row in (results["speculative"]["dense"]
                + results["speculative"]["moe"]
                + results["speculative"]["two_model"]
                + results["speculative"]["adaptive"]):
        extra = (f" acceptance={row['acceptance_rate']:.3f} "
                 f"accepted/step={row['accepted_per_step']:.2f}"
                 if row["k"] else " (baseline)")
        print(f"[serve_bench] spec {row['arch']} k={row['k']} "
              f"draft={row['draft']}: {row['decode_tok_s']:.1f} tok/s"
              + extra)

    with open(out, "w") as f:
        json.dump(results, f, indent=2)
    print(f"[serve_bench] wrote {out}")
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b",
                    choices=configs.ALL_ARCHS)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=8)
    ap.add_argument("--out", default="BENCH_serve.json")
    ap.add_argument("--archs", nargs="*", default=list(SWEEP_ARCHS),
                    help="sweep archs (dense + MoE + recurrent by default)")
    ap.add_argument("--engine-requests", type=int, default=6)
    ap.add_argument("--engine-slots", type=int, default=3)
    args = ap.parse_args()
    serve_rows(args.arch, args.batch, args.prompt_len, args.gen, args.out,
               tuple(args.archs), args.engine_requests, args.engine_slots)


if __name__ == "__main__":
    main()
