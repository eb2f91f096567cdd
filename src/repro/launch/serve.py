"""Batched serving driver: NVFP4 weights + (optional) FP8 KV cache.

Serving path = offline weight PTQ (QDQ or true-packed 4-bit) + prefill +
batched decode.  ``--weight-format packed`` serves real ``PackedNVFP4``
weights end-to-end: 2-D GEMMs stream 0.5625 B/param through the Pallas
``nvfp4_matmul`` kernel, MoE expert slabs dequantize on the fly.  CPU-
runnable at smoke scale:

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \
        --weight-format packed --batch 4 --prompt-len 16 --gen 16

``--no-smoke`` runs the full-size config.  In packed mode the driver also
replays the prompt batch through the QDQ path and reports whether the greedy
tokens agree (``--no-parity`` to skip).

``--engine`` switches from the static [B, P] batch to the continuous-
batching engine (``repro.serve``): a mixed-length request population is
submitted with staggered arrivals, scheduled into decode slots over the
config's state backend — a paged (BF16 or FP8-with-scales) KV pool for
decoder archs, constant-size per-slot state slabs for recurrent
(``--arch rwkv6-3b``, ``recurrentgemma-2b``) and encoder-conditioned
(``--arch whisper-tiny``; deterministic stub encoder frames feed both the
engine and the reference) archs — and drained; per-request greedy outputs
are checked token-for-token against single-request ``serve_batch`` runs,
and the state must drain back to empty.  Unservable configs (e.g. M-RoPE
``qwen2-vl-2b``) exit with a one-line capability error.  Engine knobs:

  --requests N            number of requests (default 8)
  --min-prompt/--max-prompt   prompt-length spread (default 4..16, >= 4x)
  --slots / --block-size / --n-blocks   decode slots and pool geometry
  --prefill-mode exact|chunked   whole-prompt (bitwise-parity) vs fixed-size
                          chunked prefill; --prefill-chunk sets the size
  --fused-kernels on|off|auto   fused serving-kernel tier: one-pass paged
                          attention + grouped NVFP4 MoE decode GEMM
                          ("auto" = paged-KV configs without --tp); greedy
                          tokens stay identical to the gather+dequant path
  --speculative K         speculative decoding (repro.spec): draft K tokens
                          per slot, verify all K+1 in one paged forward;
                          greedy output stays token-identical to the plain
                          engine (asserted by the parity check)
  --draft MODE            self-qdq | self-truncate | two-model proposer
  --draft-layers N        draft depth for self-truncate / two-model
  --adaptive-k            draft-cost-aware per-slot draft length: k adapts
                          to the measured acceptance rate and draft/verify
                          wall clock (chosen-k histogram in the stats)
  --tp N                  tensor-parallel serving over N devices (emulated
                          host devices are forced automatically when the
                          host has fewer — the CI smoke path): packed
                          codes/scales shard column-/row-parallel, the KV
                          pool shards by KV heads, and greedy engine output
                          must stay token-for-token identical to the
                          single-device reference

Exit status is nonzero if any engine invariant fails (CI runs this).
"""
from __future__ import annotations

from repro.launch import _tpenv  # noqa: F401  (isort: keep before jax)

import argparse
import dataclasses
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.core import ptq
from repro.core.nvfp4 import PackedNVFP4
from repro.launch import specs
from repro.models import common, get_model


def load_quantized(cfg, rng, weight_format: str = "qdq"):
    """'Deploy-time' weights: init BF16 then one-shot PTQ (max calibration)."""
    model = get_model(cfg)
    params = model.init_params(cfg, rng)
    qcfg = dataclasses.replace(specs.recipe_qconfig(cfg),
                               weight_format=weight_format)
    pspecs = model.param_specs(cfg)
    return ptq.quantize_weights(params, pspecs, qcfg), qcfg


def inject_quant_noise(params, scale: float):
    """Perturb every PackedNVFP4 leaf's per-tensor scale by (1 + scale).

    The numerics-drift CI canary: a deliberate calibration error that the
    shadow-teacher probes must surface (live KL up, per-layer amax
    drifted) and the snapshot gate must trip on.  Greedy engine-vs-
    ``serve_batch`` parity still holds — both sides share the perturbed
    weights — so only the NUMERICS plane sees the fault, exactly the
    failure class (quantizer drift with no crash) the gate exists for.
    """

    def bump(leaf):
        if isinstance(leaf, PackedNVFP4):
            return dataclasses.replace(
                leaf, tensor_scale=leaf.tensor_scale * (1.0 + scale))
        return leaf

    return jax.tree.map(bump, params,
                        is_leaf=lambda x: isinstance(x, PackedNVFP4))


def serve_batch(cfg, params, prompts, n_gen: int, sample_rng=None, qcfg=None,
                extras=None, forced=None, s_max: int | None = None):
    """Prefill + greedy decode ``n_gen`` tokens for a [B, P] prompt batch.

    ``qcfg`` overrides the recipe-derived serving config; serving always
    disables runtime weight fake-quant (weights are pre-quantized offline —
    re-QDQ'ing already-gridded weights would derive fresh, different scales).
    ``extras`` adds batched non-token prefill inputs (e.g. ``enc_frames``
    [B, T, d] for encoder-decoder archs).  ``forced`` [B, >= n_gen]
    teacher-forces the loop: each step feeds the forced token instead of
    the argmax, and the stats carry ``logits`` [B, n_gen, V] fp32, row i
    scoring token i.  ``s_max`` sizes the KV cache (default P + n_gen).
    """
    model = get_model(cfg)
    sq = (dataclasses.replace(qcfg, quantize_weights=False)
          if qcfg is not None else specs.serve_qconfig(cfg))
    s_max = s_max or prompts.shape[1] + n_gen

    prefill = jax.jit(lambda p, b: model.prefill(cfg, p, b, sq, s_max=s_max))
    step = jax.jit(lambda p, c, b: model.decode_step(cfg, p, c, b, sq),
                   donate_argnums=(1,))

    batch = {"tokens": prompts}
    for k, v in (extras or {}).items():
        batch[k] = jnp.asarray(v)
    t0 = time.time()
    logits, cache = prefill(params, batch)
    jax.block_until_ready(logits)
    t_prefill = time.time() - t0

    def pick(i, logits):
        if forced is None:
            return jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
        rows.append(logits[:, -1])
        return jnp.asarray(forced, jnp.int32)[:, i:i + 1]

    rows = []
    out = [pick(0, logits)]
    t0 = time.time()
    for i in range(1, n_gen):
        logits, cache = step(params, cache, {"tokens": out[-1]})
        out.append(pick(i, logits))
    jax.block_until_ready(out[-1])
    t_decode = time.time() - t0
    tokens = jnp.concatenate(out, axis=1)
    # n_gen tokens come back, but only n_gen - 1 passed through decode steps
    # (the first was sampled from the prefill logits): decode_tok_s rates the
    # decode loop alone, e2e_tok_s rates all returned tokens over prefill +
    # decode wall time.
    b = prompts.shape[0]
    stats = {"prefill_s": t_prefill, "decode_s": t_decode,
             "decode_steps": n_gen - 1, "n_tokens": b * n_gen,
             "decode_tok_s": b * (n_gen - 1) / max(t_decode, 1e-9),
             "e2e_tok_s": b * n_gen / max(t_prefill + t_decode, 1e-9)}
    if forced is not None:
        stats["logits"] = np.asarray(jnp.stack(rows, 1), np.float32)
    return tokens, stats


def teacher_forced_gap(got, want) -> dict:
    """Compare two sides' logits [T, V] for the same prompt and the same
    fed tokens (one side teacher-forced along the other's stream).

    ``rel`` is max |got - want| over the std of ``want`` (``rows`` per
    position, ``rows[0]`` the prefill row).  ``splits`` lists the
    positions where ``got``'s greedy choice is not ``want``'s, each with
    ``want``'s margin between its own choice and ``got``'s, in the same
    std units: a split is a near-tie only if that margin is small.
    """
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    std = float(want.std())
    rows = np.abs(got - want).max(-1) / std
    pick_got, pick_want = got.argmax(-1), want.argmax(-1)
    at = np.arange(len(want))
    margin = (want[at, pick_want] - want[at, pick_got]) / std
    return {"rel": float(rows.max()), "rows": rows.tolist(),
            "splits": [(int(i), float(margin[i]))
                       for i in np.flatnonzero(pick_got != pick_want)]}


def weight_report(params) -> dict:
    """Deployed weight footprint; packed GEMM weights cost ~0.5625 B/param."""
    st = common.weight_stats(params)
    st["q_bytes_per_param"] = (st["q_bytes"] / st["q_params"]
                               if st["q_params"] else 0.0)
    return st


def mixed_prompts(rng, n: int, min_len: int, max_len: int, vocab: int):
    """n prompts with lengths spread min..max (>= 4x when max >= 4*min)."""
    lens = np.linspace(min_len, max_len, n).round().astype(int)
    return [jax.random.randint(jax.random.fold_in(rng, i), (int(l),), 4,
                               vocab) for i, l in enumerate(lens)]


def obs_from_args(args):
    """Observability bundle from CLI args (None = fully disabled).

    ``--obs metrics|trace`` turns telemetry on explicitly; an output path
    implies the mode that produces it (``--trace-out`` needs the tracer,
    ``--metrics-out`` at least the registry).
    """
    mode = getattr(args, "obs", "off") or "off"
    if getattr(args, "trace_out", None):
        mode = "trace"
    elif getattr(args, "metrics_out", None) and mode == "off":
        mode = "metrics"
    if mode == "off":
        return None
    from repro.obs import Observability
    return Observability(metrics=True, trace=(mode == "trace"))


def build_engine(cfg, params, qcfg, args, mesh=None, rules=None):
    """Engine (or SpecEngine when --speculative k > 0) from CLI args."""
    from repro.serve import Engine

    bs = args.block_size
    mb = max(1, math.ceil((args.max_prompt + args.gen - 1) / bs))
    n_blocks = args.n_blocks or args.slots * mb
    prefix_cache = getattr(args, "prefix_cache", "off") == "on"
    kv_alloc = getattr(args, "kv_alloc", None) \
        or ("ondemand" if prefix_cache else "reserve")
    if (prefix_cache or kv_alloc == "ondemand") \
            and args.prefill_mode != "paged":
        # sharing and preempt-resume are only bitwise under block-granular
        # paged prefill; promote and record it so parity defaults see the
        # effective mode
        args.prefill_mode = "paged"
    args.kv_alloc = kv_alloc                  # record the resolved mode
    kw = dict(n_slots=args.slots, block_size=bs, n_blocks=n_blocks,
              max_blocks_per_slot=mb, prefill_mode=args.prefill_mode,
              prefill_chunk=args.prefill_chunk, mesh=mesh, rules=rules,
              fused_kernels=getattr(args, "fused_kernels", "auto"),
              prefix_cache=prefix_cache, kv_alloc=kv_alloc,
              headroom=getattr(args, "headroom", 2),
              obs=obs_from_args(args))
    shadow_rate = getattr(args, "shadow_rate", 0.0) or 0.0
    if shadow_rate > 0.0:
        # the BF16 teacher is the deterministic pre-quantization init
        # (same PRNGKey(0) as load_quantized) — the exact model the
        # packed student was distilled/PTQ'd from
        teacher = get_model(cfg).init_params(cfg, jax.random.PRNGKey(0))
        kw.update(shadow_teacher=teacher, shadow_rate=shadow_rate)
    spec_k = getattr(args, "speculative", 0)
    if not spec_k:
        return Engine(cfg, params, qcfg, **kw), n_blocks
    from repro.spec import SpecEngine

    draft_model = None
    if args.draft == "two-model":
        # stand-in for a small distilled student (in a real deployment the
        # QAD student drafts for its teacher): a fresh PTQ'd model at
        # draft-layers depth.  Acceptance is near-chance with random
        # weights, but greedy output must STILL match the plain engine —
        # losslessness never depends on draft quality.
        dl = args.draft_layers or max(1, cfg.n_layers // 2)
        dcfg = dataclasses.replace(cfg, n_layers=dl, name=f"{cfg.name}-2m")
        dparams, dqcfg = load_quantized(dcfg, jax.random.PRNGKey(99), "qdq")
        draft_model = (dcfg, dparams, dqcfg)
    eng = SpecEngine(cfg, params, qcfg, draft_k=spec_k, draft=args.draft,
                     draft_layers=args.draft_layers, draft_model=draft_model,
                     adaptive_k=getattr(args, "adaptive_k", False), **kw)
    return eng, n_blocks


def _partition_axes(sharding) -> tuple:
    """Flat mesh-axis names a leaf's NamedSharding actually uses."""
    spec = getattr(sharding, "spec", None)
    if spec is None:
        return ()
    out = []
    for entry in spec:
        if entry is None:
            continue
        out.extend(entry if isinstance(entry, (tuple, list)) else [entry])
    return tuple(out)


def tp_shard_report(eng) -> dict:
    """How the engine's packed weights and KV pool actually sharded.

    ``packed_total``/``packed_sharded`` count ``PackedNVFP4`` leaves whose
    codes carry a "model"-partitioned NamedSharding — the acceptance
    invariant is that column/row-parallel layers are NOT silently
    replicated.  ``kv_sharded`` says the pool pages split on the KV-head
    dim.  Byte counts are per device.
    """
    packed = [l for l in jax.tree.leaves(
        eng.params, is_leaf=lambda x: isinstance(x, PackedNVFP4))
        if isinstance(l, PackedNVFP4)]
    sharded = [p for p in packed
               if "model" in _partition_axes(p.codes.sharding)
               and "model" in _partition_axes(p.scales.sharding)]
    from repro.distributed.sharding import device_bytes
    state_data = eng.pool.data if eng.pool is not None else eng.state.data
    kv_sharded = any("model" in _partition_axes(a.sharding)
                     for a in jax.tree.leaves(state_data))
    sst = eng.state.stats()
    return {
        "packed_total": len(packed), "packed_sharded": len(sharded),
        "kv_sharded": kv_sharded,
        "weight_bytes_per_device": device_bytes(eng.params),
        "weight_bytes_total": sum(int(a.nbytes)
                                  for a in jax.tree.leaves(eng.params)),
        "kv_pool_bytes_per_device": sst["pool_bytes_per_device"],
        "kv_pool_bytes_total": sst["pool_bytes"],
    }


def _ms(v) -> str:
    """Format seconds as ms; percentiles are None (= "n/a") with no data."""
    return f"{v * 1e3:.1f}ms" if v is not None else "n/a"


def run_workload(eng, prompts, extras_list, gen: int, forced_list=None,
                 keep_logits: bool = False):
    """Submit the staggered mixed workload and drain it.

    Half the requests go in up front, the rest trickle in one engine step
    apart — deterministic, so two engines fed the same prompt list see the
    SAME arrival pattern (the basis of the cache-on/off A/B check).
    ``forced_list`` (one stream per prompt) and ``keep_logits`` go to
    ``Engine.submit``.
    """
    forced_list = forced_list or [None] * len(prompts)

    def submit(i):
        return eng.submit(np.asarray(prompts[i]), gen, extras=extras_list[i],
                          forced=forced_list[i], keep_logits=keep_logits)

    half = len(prompts) // 2
    rids = [submit(i) for i in range(half)]
    for i in range(half, len(prompts)):
        eng.step()
        rids.append(submit(i))
    outputs = eng.drain(max_steps=10_000)
    return rids, outputs


def run_engine(cfg, params, qcfg, args, mesh=None, rules=None) -> dict:
    """Serve a mixed staggered workload through the engine; verify parity
    and pool-drain invariants.  Returns a result dict (also used by CI and
    ``benchmarks.serve_bench``).

    With a TP ``mesh`` the engine shards weights + KV pool; ``params`` stays
    unsharded here, so the parity reference (single-request ``serve_batch``)
    runs on a single device — the check IS the TP acceptance oracle.
    """
    eng, n_blocks = build_engine(cfg, params, qcfg, args, mesh, rules)
    bs = args.block_size

    tp_rep = None
    if mesh is not None:
        tp_rep = tp_shard_report(eng)
        print(f"[engine] tp={dict(mesh.shape).get('model', 1)}: "
              f"packed-sharded={tp_rep['packed_sharded']}/"
              f"{tp_rep['packed_total']} kv-sharded={tp_rep['kv_sharded']} "
              f"weights/device={tp_rep['weight_bytes_per_device']/2**20:.2f}"
              f"MiB (total {tp_rep['weight_bytes_total']/2**20:.2f}MiB) "
              f"kv-pool/device={tp_rep['kv_pool_bytes_per_device']/2**20:.2f}"
              f"MiB")

    rng = jax.random.PRNGKey(1)
    prompts = mixed_prompts(rng, args.requests, args.min_prompt,
                            args.max_prompt, cfg.vocab_size)
    # encoder-conditioned archs need per-request encoder inputs; the SAME
    # deterministic frames feed the engine and the parity reference
    extras_list = [None] * len(prompts)
    if "enc_frames" in getattr(eng.state, "required_extras", ()):
        extras_list = [
            {"enc_frames": np.asarray(jax.random.normal(
                jax.random.fold_in(rng, 10_000 + i),
                (cfg.enc_seq, cfg.d_model), jnp.float32))}
            for i in range(len(prompts))]
    # staggered arrivals: half up front, the rest trickle in while the
    # first wave is already decoding
    rids, outputs = run_workload(eng, prompts, extras_list, args.gen)
    st = eng.stats()

    ok = len(outputs) == args.requests
    if not ok:
        print(f"[engine] FAIL: {len(outputs)}/{args.requests} completed")
    if eng.state.leaked():
        ok = False
        leak = (f"{eng.pool.used_blocks} pool blocks"
                if eng.pool is not None else
                f"{st.get('used_slots', '?')} state slots")
        print(f"[engine] FAIL: {leak} leaked")
    if tp_rep is not None and tp_rep["packed_total"] \
            and not tp_rep["packed_sharded"]:
        ok = False
        print("[engine] FAIL: no PackedNVFP4 leaf sharded on the model "
              "axis (silent replication)")

    # chunked prefill is numerically approximate vs whole-prompt prefill
    # (dynamic NVFP4 activation amaxes become chunk-granular), so strict
    # token parity is only asserted in exact mode unless forced
    check = (args.parity if args.parity is not None
             else args.prefill_mode == "exact")
    parity = None
    if check:
        parity = True
        # the reference must run the engine's effective packed-GEMM backend
        # (fused mode upgrades "auto" -> "grouped"), so both sides of the
        # parity check share one set of MoE GEMM numerics
        ref_qcfg = (dataclasses.replace(
            qcfg, packed_backend=eng.sq.packed_backend)
            if qcfg is not None else None)
        for rid, prompt, ex in zip(rids, prompts, extras_list):
            # reference: single-request static batch on the engine's cfg
            # (MoE archs force per-row dispatch)
            bex = ({k: v[None] for k, v in ex.items()} if ex else None)
            ref, _ = serve_batch(eng.cfg, params, prompt[None], args.gen,
                                 qcfg=ref_qcfg, extras=bex)
            if not np.array_equal(np.asarray(ref[0]), outputs[rid]):
                parity = False
                print(f"[engine] FAIL: request {rid} diverges from "
                      f"serve_batch: {outputs[rid][:8].tolist()} vs "
                      f"{np.asarray(ref[0][:8]).tolist()}")
        ok = ok and parity

    # prefix-cache A/B: the SAME workload through a second engine with the
    # cache off (identical paged prefill + allocation mode) must produce
    # bitwise-identical greedy streams and also drain leak-free — block
    # sharing, COW, eviction and preempt-resume are all invisible in the
    # token plane or this fails the run
    cache_parity = None
    if getattr(args, "prefix_cache", "off") == "on" \
            and args.parity is not False:
        base_args = argparse.Namespace(**vars(args))
        base_args.prefix_cache = "off"
        base_args.obs = "off"
        base_args.metrics_out = base_args.trace_out = None
        base_args.shadow_rate = 0.0
        base_eng, _ = build_engine(cfg, params, qcfg, base_args, mesh, rules)
        base_rids, base_out = run_workload(base_eng, prompts, extras_list,
                                           args.gen)
        cache_parity = len(base_out) == len(outputs)
        for rid, brid in zip(rids, base_rids):
            if not np.array_equal(outputs.get(rid, np.empty(0, np.int32)),
                                  base_out.get(brid,
                                               np.empty(0, np.int32))):
                cache_parity = False
                print(f"[engine] FAIL: request {rid} cache-on diverges "
                      f"from cache-off: "
                      f"{outputs.get(rid, [])[:8].tolist()} vs "
                      f"{base_out.get(brid, [])[:8].tolist()}")
        if base_eng.state.leaked():
            cache_parity = False
            print("[engine] FAIL: cache-off baseline leaked pool blocks")
        ok = ok and cache_parity

    spec = getattr(args, "speculative", 0)
    drained = not eng.state.leaked()
    pool_desc = (f"pool={n_blocks}x{bs}" if eng.pool is not None else
                 f"state-slabs={st.get('state_bytes_per_slot', 0)}B/slot")
    print(f"[engine] arch={cfg.name} "
          f"state-plan={'+'.join(eng.state_plan)} "
          f"requests={args.requests} "
          f"prompts={args.min_prompt}..{args.max_prompt} gen={args.gen} "
          f"slots={args.slots} {pool_desc} "
          f"prefill={args.prefill_mode} "
          f"fused-kernels={'on' if st['fused_kernels'] else 'off'}"
          f"/{st['packed_backend']}"
          + (f" speculative=k{spec}/{args.draft}" if spec else ""))
    print(f"[engine] decode={st['decode_tok_s']:.1f} tok/s "
          f"e2e={st['e2e_tok_s']:.1f} tok/s "
          f"peak-pool-util={st['peak_utilization']:.2f} "
          f"steps={st['steps']} "
          f"ttft_p50={_ms(st['ttft_p50_s'])} "
          f"ttft_p95={_ms(st['ttft_p95_s'])} "
          f"tok_lat_p50={_ms(st['decode_lat_p50_s'])} "
          f"tok_lat_p95={_ms(st['decode_lat_p95_s'])} "
          f"parity={'AGREE' if parity else ('skipped' if parity is None else 'DISAGREE')} "
          f"state-drained={drained}")
    cache_st = None
    if getattr(args, "prefix_cache", "off") == "on":
        cache_st = eng.state.stats().get("prefix_cache") or {}
        cp_s = ("AGREE" if cache_parity
                else ("skipped" if cache_parity is None else "DISAGREE"))
        print(f"[engine] prefix-cache: hits={cache_st.get('hits', 0)} "
              f"misses={cache_st.get('misses', 0)} "
              f"evictions={cache_st.get('evictions', 0)} "
              f"preempts={st.get('preempts', 0)} "
              f"kv-alloc={getattr(args, 'kv_alloc', 'reserve')} "
              f"cache-off-parity={cp_s}")
    if spec:
        adaptive = (f" chosen-k={st['chosen_k_hist']}"
                    if st.get("adaptive_k") else "")
        acc = st["acceptance_rate"]
        aps = st["accepted_per_step"]
        acc_s = f"{acc:.3f}" if acc is not None else "n/a"
        aps_s = f"{aps:.2f}" if aps is not None else "n/a"
        print(f"[engine] speculative: acceptance={acc_s} "
              f"accepted/step={aps_s} "
              f"drafted={st['drafted_tokens']} "
              f"rolled-back={st['rolled_back_tokens']} "
              f"verify-steps={st['verify_steps']}{adaptive}")

    if eng.numerics is not None:
        ns = eng.numerics.summary()
        kl_pts = ns["series"].get("qad_live_kl", [])
        kl_s = f"{kl_pts[-1][1]:.4f}" if kl_pts else "n/a"
        sq = ns["sqnr_db_min"]
        sq_s = f"{sq:.1f}dB" if sq is not None else "n/a"
        print(f"[numerics] shadow-steps={eng.shadow_steps} "
              f"rate=1/{eng._shadow_every} "
              f"records={ns['sampled_records']} "
              f"live_kl={kl_s} sqnr_min={sq_s}")

    if eng.obs.enabled:
        from repro.obs import export as obs_export
        qw = eng.obs.metrics.get("serve_queue_wait_seconds")
        gemms = eng.obs.metrics.get("qeinsum_dispatch_total")
        backends = ""
        if gemms is not None:
            backends = " qeinsum=" + ",".join(
                f"{e['labels']['backend']}:{int(e['value'])}"
                for e in gemms.snapshot().get("labels", []))
        print(f"[metrics] enabled "
              f"queue_wait_p50={_ms(qw.percentile(50) if qw else None)}"
              f"{backends} "
              f"trace_events={len(eng.obs.trace.events)}")
        if getattr(args, "metrics_out", None):
            obs_export.write_metrics(eng, args.metrics_out)
            print(f"[metrics] wrote {args.metrics_out} (+ .prom)")
        if getattr(args, "trace_out", None):
            obs_export.write_trace(eng, args.trace_out)
            print(f"[metrics] wrote {args.trace_out}")

    return {"ok": ok, "outputs": outputs, "stats": st,
            "tokens_match_serve_batch": parity, "n_blocks": n_blocks,
            "pool_drained": drained, "tp": tp_rep, "obs": eng.obs.enabled,
            "tokens_match_cache_off": cache_parity,
            "prefix_cache": cache_st}


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b", choices=configs.ALL_ARCHS)
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True, help="reduced config (--no-smoke = full size)")
    ap.add_argument("--weight-format", choices=("qdq", "packed"),
                    default="qdq")
    ap.add_argument("--parity", action=argparse.BooleanOptionalAction,
                    default=None, help="packed mode: also run the QDQ path "
                    "and compare greedy tokens; engine mode: compare each "
                    "request against serve_batch (default: on)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    # --- continuous-batching engine mode ---
    ap.add_argument("--engine", action="store_true",
                    help="serve a mixed-length staggered workload through "
                    "the repro.serve continuous-batching engine")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--min-prompt", type=int, default=4)
    ap.add_argument("--max-prompt", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--n-blocks", type=int, default=0,
                    help="pool blocks (0 = slots * blocks-per-request)")
    ap.add_argument("--prefill-mode", choices=("exact", "chunked", "paged"),
                    default="exact",
                    help="exact = whole-prompt (bitwise vs serve_batch); "
                    "chunked = fixed-size approximate chunks; paged = "
                    "block-granular token-causal prefill straight into the "
                    "pool (every block's bytes depend only on its token "
                    "prefix — the mode prefix caching and preemption need)")
    ap.add_argument("--prefill-chunk", type=int, default=8)
    ap.add_argument("--prefix-cache", choices=("on", "off"), default="off",
                    help="content-hashed block-granular prefix cache over "
                    "the paged KV pool: retired blocks park keyed by their "
                    "token prefix and later requests reuse them without "
                    "recompute; forces --prefill-mode paged and (unless "
                    "--kv-alloc says otherwise) on-demand allocation. "
                    "Greedy output stays bitwise identical to cache-off "
                    "(checked unless --no-parity)")
    ap.add_argument("--kv-alloc", choices=("reserve", "ondemand"),
                    default=None,
                    help="pool allocation policy: 'reserve' books the "
                    "worst-case block count at admission; 'ondemand' books "
                    "only what the prompt needs and grows block-by-block "
                    "at decode, evicting cache LRU and then preempting the "
                    "lowest-progress request under pressure (default: "
                    "ondemand when --prefix-cache on, else reserve)")
    ap.add_argument("--headroom", type=int, default=2,
                    help="on-demand admission watermark: free+evictable "
                    "blocks that must remain AFTER admitting a request "
                    "(waived when the pool is idle so one big request "
                    "can always start)")
    ap.add_argument("--fused-kernels", choices=("on", "off", "auto"),
                    default="auto",
                    help="fused serving-kernel tier: one-pass paged "
                    "attention (page gather + FP8 dequant + attend in one "
                    "Pallas launch) and grouped NVFP4 MoE decode GEMM. "
                    "'auto' enables it for paged-KV configs without --tp; "
                    "greedy output stays bitwise identical either way")
    # --- speculative decoding (repro.spec, engine mode only) ---
    ap.add_argument("--speculative", type=int, default=0, metavar="K",
                    help="draft length k per verify step (0 = off); greedy "
                    "outputs stay token-identical to the plain engine")
    ap.add_argument("--draft", choices=("self-qdq", "self-truncate",
                                        "two-model"), default="self-qdq",
                    help="draft proposer: the target's own QDQ forward, its "
                    "first --draft-layers layers, or a separate small "
                    "student model")
    ap.add_argument("--draft-layers", type=int, default=0,
                    help="draft depth for self-truncate / two-model "
                    "(0 = half the target)")
    ap.add_argument("--adaptive-k", action="store_true",
                    help="draft-cost-aware per-slot draft length: adapt k "
                    "from the measured acceptance rate and draft/verify "
                    "wall clock (requires --speculative)")
    # --- observability (repro.obs, engine mode) ---
    ap.add_argument("--obs", choices=("off", "metrics", "trace"),
                    default="off",
                    help="serving telemetry: 'metrics' = counters/gauges/"
                    "latency histograms + dispatch counts; 'trace' adds the "
                    "request-lifecycle tracer (Chrome-trace export). "
                    "Greedy tokens are bitwise identical in every mode")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the repro.obs.metrics/v1 JSON snapshot here "
                    "(plus Prometheus text at the sibling .prom path); "
                    "implies at least --obs metrics")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the Chrome-trace/Perfetto JSON here; "
                    "implies --obs trace")
    # --- numerics observability (repro.obs.numerics, engine mode) ---
    ap.add_argument("--shadow-rate", type=float, default=0.0, metavar="R",
                    help="shadow-teacher sampling rate: on ~R of decode "
                    "steps, re-forward each running request's context "
                    "through the BF16 teacher and the quantized student "
                    "and record live KL / top-1 agreement plus per-layer "
                    "divergence and quant-error stats (0 = off; stateless, "
                    "token streams are unchanged)")
    ap.add_argument("--inject-quant-noise", type=float, default=0.0,
                    metavar="SCALE",
                    help="CI canary: perturb every packed weight's "
                    "per-tensor scale by (1 + SCALE) so the numerics "
                    "gate has a fault to trip on (requires "
                    "--weight-format packed)")
    # --- tensor parallelism (engine mode) ---
    ap.add_argument("--tp", type=int, default=1, metavar="N",
                    help="tensor-parallel degree: shard packed codes/scales "
                    "column-/row-parallel and the paged KV pool by KV heads "
                    "over a (data, model=N) mesh; emulated host devices are "
                    "forced automatically when needed (CI smoke path)")
    return ap


def main(argv=None):
    args = build_parser().parse_args(argv)

    if args.adaptive_k and not args.speculative:
        raise SystemExit("--adaptive-k requires --speculative K (it adapts "
                         "the draft length)")
    if (args.obs != "off" or args.metrics_out or args.trace_out) \
            and not args.engine:
        raise SystemExit("--obs/--metrics-out/--trace-out require --engine "
                         "(telemetry instruments the serving engine)")
    if args.shadow_rate and not args.engine:
        raise SystemExit("--shadow-rate requires --engine (the shadow "
                         "teacher samples the engine's decode loop)")
    if (args.prefix_cache == "on" or args.kv_alloc) and not args.engine:
        raise SystemExit("--prefix-cache/--kv-alloc require --engine (they "
                         "configure the paged serving pool)")
    if args.inject_quant_noise and args.weight_format != "packed":
        raise SystemExit("--inject-quant-noise perturbs PackedNVFP4 "
                         "tensor scales; use --weight-format packed")

    mesh = rules = None
    if args.tp > 1:
        if not args.engine:
            raise SystemExit("--tp requires --engine (TP serving is an "
                             "engine path)")
        from repro.distributed import sharding as shd
        from repro.launch.mesh import make_host_mesh
        n_dev = len(jax.devices())
        if n_dev % args.tp:
            raise SystemExit(f"--tp {args.tp} does not divide the "
                             f"{n_dev} visible devices (set XLA_FLAGS="
                             f"--xla_force_host_platform_device_count="
                             f"{args.tp} before jax initializes)")
        mesh = make_host_mesh(model_parallel=args.tp)
        rules = shd.make_rules(mesh, "tp_only")
        print(f"[serve] tp={args.tp} mesh={dict(mesh.shape)}")

    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get_config(args.arch)
    rng = jax.random.PRNGKey(0)
    params, qcfg = load_quantized(cfg, rng, weight_format=args.weight_format)
    if args.inject_quant_noise:
        params = inject_quant_noise(params, args.inject_quant_noise)
        print(f"[serve] CANARY: packed tensor scales perturbed by "
              f"{args.inject_quant_noise:+.0%}")
    wr = weight_report(params)
    if wr["q_params"]:
        print(f"[serve] weights: total={wr['total_bytes']/2**20:.2f}MiB  "
              f"quantized-gemm={wr['q_bytes']/2**20:.2f}MiB over "
              f"{wr['q_params']/1e6:.2f}M params "
              f"({wr['q_bytes_per_param']:.4f} B/param; bf16 would be 2.0)")
    else:
        print(f"[serve] weights: total={wr['total_bytes']/2**20:.2f}MiB, "
              f"all dense (qdq stores quantized values as BF16, 2 B/param)")

    if args.engine:
        from repro.serve import UnsupportedStateError
        try:
            res = run_engine(cfg, params, qcfg, args, mesh=mesh, rules=rules)
        except UnsupportedStateError as e:
            # capability probe said no (e.g. vision_prefix / M-RoPE): a
            # clear one-line refusal, not a traceback
            raise SystemExit(f"[serve] unsupported: {e}") from None
        res["weights"] = wr
        if not res["ok"]:
            raise SystemExit(1)
        return res

    prompts = jax.random.randint(rng, (args.batch, args.prompt_len), 4,
                                 cfg.vocab_size)
    toks, stats = serve_batch(cfg, params, prompts, args.gen)
    print(f"[serve] arch={cfg.name} batch={args.batch} "
          f"format={args.weight_format} "
          f"prefill={stats['prefill_s']*1e3:.1f}ms "
          f"decode={stats['decode_tok_s']:.1f} tok/s "
          f"e2e={stats['e2e_tok_s']:.1f} tok/s")
    print("[serve] sample:", toks[0, :12].tolist())

    result = {"tokens": toks, "stats": stats, "weights": wr}
    parity = (args.weight_format == "packed"
              if args.parity is None else args.parity)
    if parity and args.weight_format != "packed":
        print("[serve] --parity only applies to --weight-format packed; "
              "nothing to compare")
    if parity and args.weight_format == "packed":
        qdq_params, _ = load_quantized(cfg, rng, weight_format="qdq")
        ref_toks, _ = serve_batch(cfg, qdq_params, prompts, args.gen)
        match = bool(jnp.all(toks == ref_toks))
        print(f"[serve] packed-vs-qdq greedy tokens "
              f"{'AGREE' if match else 'DISAGREE'}")
        result["tokens_match_qdq"] = match
    return result


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
