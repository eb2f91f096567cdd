"""On-chip smoke test: NVFP4 serving and the QAD step at qwen1.5-0.5b width.

    python chip_smoke.py             # one TPU chip: kernels, serve, train
    python chip_smoke.py --chips 4   # four chips: TP=4 serving and the
                                     # FSDPxTP QAD step, each against one chip

Drives the repo's own entry points (``repro.launch.serve``'s engine path,
``repro.launch.train``'s QAD step) at the full published width of
qwen1.5-0.5b (24 layers, d 1024, 16/16 heads, d_ff 2816, vocab 151936) with
random weights from fixed seeds.  Each phase prints one line: its verdict,
its compile time (trace + lowering + XLA, set-up and not a speed, with the
persistent-cache hits), the device's ``peak_bytes_in_use`` so far, and the
Pallas kernels it traced — every one of them lowered through Mosaic, since
the script refuses to start when kernels would be interpreted.

Everything runs in this one process: a chip belongs to one process at a
time.  With no TPU attached (or ``REPRO_PALLAS_INTERPRET`` set) it exits
nonzero after one line naming the reason and prints no result.  The last
stdout line is ``{"ok": true, "device": {...}}`` only when every phase
passed; any failure exits nonzero.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time
import traceback
from pathlib import Path

ARCH = "qwen1.5-0.5b"
# serving traffic: 8 requests, prompts 128..1024 tokens, 32 generated each,
# 4 decode slots over a 264 x 16-token paged pool
SERVE_ARGV = ["--arch", ARCH, "--no-smoke", "--weight-format", "packed",
              "--engine", "--requests", "8", "--min-prompt", "128",
              "--max-prompt", "1024", "--gen", "32", "--slots", "4"]
TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ = 3, 4, 1024
TP = 4
# the four-chip gate serves the same traffic cut to 4 requests (each
# distinct prompt length compiles a prefill on both engines; four-chip
# time costs four times as much); its NVFP4-activation reading takes 2
TP_REQUESTS, TP_A4_REQUESTS = 4, 2

# Logit tolerance for two runs of the same model that differ only in the
# order of their fp32 sums: max |dlogit| over the reference logits' std,
# teacher-forced along one stream.  Serving: the engine (fused paged
# attention, 4-slot batches, a 1056-key page strip) against serve_batch's
# dense one-row loop.  TP: the row-parallel wo / wd psum four K/4 partials
# where one chip accumulates K tiles.  Such reorderings move BF16
# logits little: at 2 layers on CPU the dequant-einsum backend in place of
# the Pallas kernel moves them 0.03 std, and through 24 layers such errors
# grow at most linearly.  Defects are of another order: at 2 layers a lost
# KV page moves them 4-5 std (tests/test_engine.py) and a row-parallel
# shard reading its neighbour's block scales 2.6-3 std (tests/test_tp.py).
# The gates run with BF16 activations: NVFP4 activation quantization rounds
# each row to a dynamic per-row scale, a discontinuous step that turns
# such reorderings into a different forward pass at depth (four chips
# with NVFP4 activations moved the prefill row 1.2-1.6 std).  The served
# configuration, NVFP4 activations, is checked on what must hold exactly
# and its logit gap is reported.  A greedy split is allowed only where
# the reference's own margin between the two tokens is inside the
# tolerance too.
LOGIT_TOL = 0.5
FSDP_KL_RTOL, FSDP_KL_ATOL = 5e-2, 1e-4


def _fail(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)
    sys.exit(1)


def _require_chip(n_chips: int):
    """The chip checks every phase depends on; exits with one line."""
    if "REPRO_PALLAS_INTERPRET" in os.environ:
        _fail("refusing to run: REPRO_PALLAS_INTERPRET is set, and this "
              "smoke checks the Mosaic-lowered kernels")
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        _fail(f"no TPU: jax found {len(devices)} {devices[0].platform} "
              "device(s) and no accelerator")
    if len(devices) < n_chips:
        _fail(f"--chips {n_chips} needs {n_chips} TPU chips; jax found "
              f"{len(devices)}")
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    try:
        from repro.kernels import ops
    except ImportError as e:
        _fail(f"the repro package is not next to this script: {e}")
    if ops.interpret_default():
        _fail("Pallas kernels would run in interpret mode on this device")
    return devices[:n_chips]


class CompileClock:
    """Sums jax's compile-duration events (trace, lowering to MLIR, XLA
    compile or persistent-cache fetch) and counts persistent-cache hits."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds, self.hits, self.requests = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1

    def snapshot(self):
        return self.seconds, self.hits, self.requests


def run_phase(name, fn, clock, devices) -> bool:
    """Run one phase, catching its failure into the verdict, and print the
    phase line."""
    from repro.obs import dispatch as obs_dispatch
    from repro.obs.metrics import MetricsRegistry

    print(f"[smoke] {name}: start", flush=True)
    reg = MetricsRegistry()
    c0, h0, r0 = clock.snapshot()
    t0 = time.monotonic()
    try:
        with obs_dispatch.recording(obs_dispatch.DispatchRecorder(reg)):
            ok, detail = fn()
    except SystemExit as e:
        ok, detail = False, f"exited with {e.code}"
    except Exception:
        traceback.print_exc()
        ok, detail = False, "raised (traceback above)"
    wall = time.monotonic() - t0
    c1, h1, r1 = clock.snapshot()
    peak = max(d.memory_stats()["peak_bytes_in_use"] for d in devices)

    def counts(metric, label):
        m = reg.get(metric)
        cells = m.snapshot().get("labels", []) if m is not None else []
        return ",".join(f"{c['labels'][label]}:{int(c['value'])}"
                        for c in cells) or "none"

    print(f"[smoke] {name}: {'PASS' if ok else 'FAIL'} {detail} "
          f"wall={wall:.1f}s compile={c1 - c0:.1f}s (set-up, not a speed) "
          f"cache-hits={h1 - h0}/{r1 - r0} peak_hbm={peak / 2**30:.2f}GiB "
          f"lowered={counts('kernel_dispatch_total', 'kernel')} "
          f"gemm={counts('qeinsum_dispatch_total', 'backend')}", flush=True)
    return ok


def model_config():
    from repro import configs
    return configs.get_config(ARCH)


def serve_args(extra):
    from repro.launch import serve
    return serve.build_parser().parse_args(SERVE_ARGV + extra)


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------


def phase_kernels():
    """The decode GEMMs and fused paged attention, compiled for the chip,
    against their oracles."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import nvfp4
    from repro.kernels import ops, ref
    from repro.models import attention as attn

    cfg = model_config()
    args = serve_args([])
    hd, nh, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    gemms = {"wqkv": (cfg.d_model, (nh + 2 * nkv) * hd),
             "wo": (nh * hd, cfg.d_model),
             "wg": (cfg.d_model, cfg.d_ff),
             "wd": (cfg.d_ff, cfg.d_model)}
    key = jax.random.PRNGKey(0)
    f32 = jnp.float32
    ok, notes = True, []

    def lowered(fn, *a):
        compiled = jax.jit(fn).lower(*a).compile()
        return compiled, "tpu_custom_call" in compiled.as_text()

    for i, (name, (k, n)) in enumerate(gemms.items()):
        kk = jax.random.fold_in(key, i)
        w = jax.random.normal(kk, (k, n), f32) * 0.05
        packed = ops.pack_weight(w)
        x = jax.random.normal(jax.random.fold_in(kk, 1), (args.slots, k),
                              jnp.bfloat16)
        compiled, mosaic = lowered(
            lambda x, p: ops.nvfp4_matmul(x, p, out_dtype=f32), x, packed)
        got = np.asarray(compiled(x, packed))
        want = np.asarray(ref.nvfp4_matmul_ref(x, packed, out_dtype=f32))
        # fp32 reassociation bound: 2 K u sum|x_i w_i| (u = 2^-24)
        w_dq = nvfp4.unpack(packed, dtype=jnp.bfloat16).astype(f32)
        mag = np.asarray(jnp.dot(jnp.abs(x.astype(f32)), jnp.abs(w_dq).T,
                                 precision="highest"))
        ratio = float(np.max(np.abs(got - want) / (2 * k * 2**-24 * mag
                                                   + 1e-30)))
        # a decode row's result must not depend on the rows batched with it
        one = np.asarray(jax.jit(
            lambda x, p: ops.nvfp4_matmul(x, p, out_dtype=f32))(x[:1],
                                                                packed))
        row_inv = bool(np.array_equal(one, got[:1]))
        good = mosaic and row_inv and ratio <= 1.0
        ok &= good
        notes.append(f"{name}:K={k},N={n},mosaic={mosaic},"
                     f"row-invariant={row_inv},err/bound={ratio:.3g}")

    bs = args.block_size
    mb = math.ceil((args.max_prompt + args.gen - 1) / bs)
    b = args.slots
    n_blocks = b * mb + 2
    for s_q in (1, 4):                    # decode, and k+1 verify with k=3
        kk = jax.random.fold_in(key, 100 + s_q)
        # two layers of the stored pool layout; the kernel reads layer 1
        pool = {name: jax.random.normal(jax.random.fold_in(kk, j),
                                        (2, n_blocks, bs, nkv * hd), f32
                                        ).astype(jnp.bfloat16)
                for j, name in enumerate(("k", "v"))}
        layer = jnp.int32(1)
        bt = jax.random.permutation(jax.random.fold_in(kk, 2), n_blocks
                                    )[: b * mb].reshape(b, mb).astype(
                                        jnp.int32)
        base = jax.random.randint(jax.random.fold_in(kk, 3), (b,), s_q,
                                  mb * bs + 1)
        pos = base if s_q == 1 else (base[:, None] - s_q + 1
                                     + jnp.arange(s_q)[None, :]).astype(
                                         jnp.int32)
        q = jax.random.normal(jax.random.fold_in(kk, 4),
                              (b, s_q, nh, hd)).astype(jnp.bfloat16)
        compiled, mosaic = lowered(attn.paged_attend_fused, q, pool, layer,
                                   bt, pos)
        got = np.asarray(compiled(q, pool, layer, bt, pos), np.float32)
        oracle = jax.jit(attn.paged_attend)
        want = np.asarray(oracle(q, pool, layer, bt, pos), np.float32)
        # the oracle's one-query dots are lowered by XLA as multiply+reduce,
        # the kernel's on the MXU: they may differ by the bf16 roundings of
        # p and of the output, each within 2^-8 of sum_i p_i |v_i| (that
        # sum is the oracle attending over |v|).  With k+1 queries both
        # sides use the MXU and must agree bit for bit.
        mag = np.asarray(oracle(q, dict(pool, v=jnp.abs(pool["v"])), layer,
                                bt, pos), np.float32)
        ratio = float(np.max(np.abs(got - want) / (2**-7 * mag + 1e-30)))
        good = mosaic and (ratio <= 1.0 if s_q == 1 else ratio == 0.0)
        ok &= good
        notes.append(f"paged_attention:q_len={s_q},pages={mb}x{bs},"
                     f"mosaic={mosaic},bitwise={ratio == 0.0},"
                     f"err/bound={ratio:.3g}")
    for note in notes:
        print(f"[smoke] kernels: {note}", flush=True)
    return ok, f"{len(notes)} kernel cases"


def gap_line(gap) -> str:
    """max |dlogit| / std over all rows and in the prefill row, and the
    greedy splits with the reference's margin at each."""
    splits = ",".join(f"{i}@{m:.3g}" for i, m in gap["splits"]) or "none"
    return (f"max|dlogit|/std={gap['rel']:.4g} "
            f"prefill_row={gap['rows'][0]:.4g} splits(pos@margin)={splits}")


def gap_ok(gap) -> bool:
    return (gap["rel"] <= LOGIT_TOL
            and all(m <= LOGIT_TOL for _, m in gap["splits"]))


def phase_serve():
    """Continuous-batching engine on packed NVFP4 weights with the fused
    kernels, mixed staggered traffic, its own logits kept, against
    ``serve_batch`` teacher-forced along the engine's token streams.

    With BF16 activations (NVFP4 weights) the gate is on logits: within
    ``LOGIT_TOL`` in every row, splits only at near-ties.  That checks the
    engine's paging, block tables, positions, masks and fused kernels
    against the dense loop.  In the served configuration (NVFP4
    activations too) the gate is what holds exactly: the prefill row,
    computed by the same prefill program on both sides, agrees; every
    request gives the same tokens under mixed traffic as alone through the
    same engine (per-row activation scales do not leak between slots); the
    pool drains.  Its decode-row gap is reported, not gated (see
    ``LOGIT_TOL``).
    """
    import jax
    import numpy as np

    from repro.launch import serve

    cfg = model_config()
    args = serve_args(["--fused-kernels", "on"])
    params, qcfg = serve.load_quantized(cfg, jax.random.PRNGKey(0), "packed")
    prompts = [np.asarray(p) for p in serve.mixed_prompts(
        jax.random.PRNGKey(1), args.requests, args.min_prompt,
        args.max_prompt, cfg.vocab_size)]
    ok, notes = True, []
    for acts in ("nvfp4", "bf16"):
        q = dataclasses.replace(qcfg, quantize_activations=acts == "nvfp4")
        eng, _ = serve.build_engine(cfg, params, q, args)
        rids, mixed = serve.run_workload(eng, prompts, [None] * len(prompts),
                                         args.gen, keep_logits=True)
        ref_q = dataclasses.replace(q, packed_backend=eng.sq.packed_backend)
        gaps = [serve.teacher_forced_gap(eng.logits(rid), serve.serve_batch(
            eng.cfg, params, prompt[None], args.gen, qcfg=ref_q,
            forced=mixed[rid][None], s_max=eng.s_alloc)[1]["logits"][0])
            for rid, prompt in zip(rids, prompts)]
        for rid, gap in zip(rids, gaps):
            print(f"[smoke] serve: activations={acts} request {rid} "
                  f"prompt={len(prompts[rid])} {gap_line(gap)}", flush=True)
        worst = max(g["rel"] for g in gaps)
        fused = eng.stats()["fused_kernels"]
        drained = not eng.state.leaked()
        ok &= fused and drained
        if acts == "bf16":
            good = all(gap_ok(g) for g in gaps)
            notes.append(f"bf16-acts: max|dlogit|/std={worst:.4g} "
                         f"(tol {LOGIT_TOL}) splits="
                         f"{sum(len(g['splits']) for g in gaps)} "
                         f"logits_ok={good}")
            ok &= good
            continue
        prefill = max(g["rows"][0] for g in gaps)
        bitwise = sum(g["rows"][0] == 0.0 for g in gaps)
        alone = {}
        for rid, prompt in zip(rids, prompts):
            one = eng.submit(prompt, args.gen)
            alone[rid] = eng.drain(max_steps=10_000)[one]
        invariant = sum(np.array_equal(mixed[r], alone[r]) for r in rids)
        drained = not eng.state.leaked()
        ok &= prefill <= LOGIT_TOL and invariant == len(rids) and drained
        notes.append(f"served(nvfp4-acts): prefill_row max|dlogit|/std="
                     f"{prefill:.4g} (tol {LOGIT_TOL}, bitwise "
                     f"{bitwise}/{len(rids)}) batching_invariant="
                     f"{invariant}/{len(rids)} pool_drained={drained} "
                     f"decode max|dlogit|/std={worst:.4g} (reported) "
                     f"fused={fused}/{eng.sq.packed_backend}")
    return ok, f"requests={len(prompts)} " + " | ".join(notes)


def phase_train():
    """QAD steps (BF16 teacher, NVFP4-QDQ student, AdamW) at full width."""
    import numpy as np

    from repro.launch import train

    _, hist = train.train(ARCH, smoke=False, steps=TRAIN_STEPS,
                          batch=TRAIN_BATCH, seq=TRAIN_SEQ, eval_every=1)
    vals = [h[k] for h in hist for k in ("loss", "kl", "ce", "grad_norm")]
    finite = len(hist) == TRAIN_STEPS and bool(np.all(np.isfinite(vals)))
    last = hist[-1]
    return finite, (f"steps={len(hist)} batch={TRAIN_BATCH}x{TRAIN_SEQ} "
                    f"loss={last['loss']:.6g} kl={last['kl']:.6g} "
                    f"grad_norm={last['grad_norm']:.6g} finite={finite}")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def phase_tp():
    """TP=4 engine against the one-chip engine under the same settings
    (fused kernels off, same packed backend): the mesh is the only
    difference.  The one-chip engine runs the traffic greedily and keeps
    its logits; the TP engine is teacher-forced along those streams and
    keeps its own.  Gate, with BF16 activations: within ``LOGIT_TOL`` in
    every row, splits only at near-ties, packed weights and KV pool
    sharded, both pools drained.  With NVFP4 activations the same TP gap
    is reported beside a one-chip engine on the dequant-einsum backend,
    which reorders the GEMM sums and nothing else."""
    import jax
    import numpy as np

    from repro.distributed import sharding as shd
    from repro.launch import serve
    from repro.launch.mesh import make_host_mesh

    cfg = model_config()
    params, qcfg = serve.load_quantized(cfg, jax.random.PRNGKey(0), "packed")
    mesh = make_host_mesh(model_parallel=TP)
    rules = shd.make_rules(mesh, "tp_only")
    ok, notes = True, []
    for acts, n in (("bf16", TP_REQUESTS), ("nvfp4", TP_A4_REQUESTS)):
        args = serve_args(["--fused-kernels", "off", "--requests", str(n)])
        q = dataclasses.replace(qcfg, quantize_activations=acts == "nvfp4")
        prompts = [np.asarray(p) for p in serve.mixed_prompts(
            jax.random.PRNGKey(1), n, args.min_prompt, args.max_prompt,
            cfg.vocab_size)]
        none = [None] * n

        def run(q, forced=None, m=None, r=None):
            eng, _ = serve.build_engine(cfg, params, q, args, m, r)
            rids, out = serve.run_workload(eng, prompts, none, args.gen,
                                           forced, keep_logits=True)
            ok_run = len(out) == n and not eng.state.leaked()
            return eng, [out[i] for i in rids], \
                [eng.logits(i) for i in rids], ok_run

        one, streams, l1, ok1 = run(q)
        tp, _, l4, ok4 = run(q, streams, mesh, rules)
        rep = serve.tp_shard_report(tp)
        sharded = (rep["packed_total"] > 0 and rep["kv_sharded"]
                   and rep["packed_sharded"] == rep["packed_total"])
        same = (not one.fused and not tp.fused
                and one.sq.packed_backend == tp.sq.packed_backend)
        gaps = [serve.teacher_forced_gap(b, a) for a, b in zip(l1, l4)]
        for i, gap in enumerate(gaps):
            print(f"[smoke] tp: activations={acts} request {i} "
                  f"prompt={len(prompts[i])} {gap_line(gap)}", flush=True)
        worst = max(g["rel"] for g in gaps)
        ok &= ok1 and ok4 and sharded and same
        if acts == "bf16":
            good = all(gap_ok(g) for g in gaps)
            ok &= good
            notes.append(
                f"bf16-acts: same_settings={same} packed_sharded="
                f"{rep['packed_sharded']}/{rep['packed_total']} "
                f"kv_sharded={rep['kv_sharded']} weights/device="
                f"{rep['weight_bytes_per_device'] / 2**20:.1f}MiB "
                f"max|dlogit|/std={worst:.4g} (tol {LOGIT_TOL}) splits="
                f"{sum(len(g['splits']) for g in gaps)} logits_ok={good}")
            continue
        base_q = dataclasses.replace(q, packed_backend="dequant")
        _, _, lb, okb = run(base_q, streams)
        ok &= okb
        base = max(serve.teacher_forced_gap(b, a)["rel"]
                   for a, b in zip(l1, lb))
        notes.append(f"nvfp4-acts (reported): tp max|dlogit|/std="
                     f"{worst:.4g} one-chip reordered (dequant backend) "
                     f"max|dlogit|/std={base:.4g}")
    return ok, f"mesh={dict(mesh.shape)} " + " | ".join(notes)


def phase_fsdp_tp():
    """One QAD step on a (data 2, model 2) mesh against the same step on
    one chip."""
    import jax
    import numpy as np

    from repro.core import qad
    from repro.data import DataConfig, make_batch
    from repro.distributed import ctx as shd_ctx
    from repro.distributed import sharding as shd
    from repro.launch import specs
    from repro.launch.mesh import make_host_mesh
    from repro.models import get_model
    from repro.optim import AdamW

    cfg = model_config()
    model = get_model(cfg)
    opt = AdamW(lr=1e-3)
    qcfg = specs.recipe_qconfig(cfg)
    batch = make_batch(DataConfig(vocab_size=cfg.vocab_size,
                                  seq_len=TRAIN_SEQ,
                                  global_batch=TRAIN_BATCH), 0)
    state = qad.init_state(model, cfg, jax.random.PRNGKey(0), opt)
    step = qad.make_train_step(model, cfg, qcfg, opt)
    _, m1 = jax.jit(step)(state, batch)

    mesh = make_host_mesh(model_parallel=2)
    rules = shd.make_rules(mesh, "fsdp_tp")
    shard_p = shd.tree_shardings(model.param_specs(cfg), mesh, rules)
    with shd_ctx.use(mesh, rules):
        state_sh = qad.TrainState(
            step=state.step,
            student=jax.device_put(state.student, shard_p),
            teacher=jax.device_put(state.teacher, shard_p),
            opt_state=state.opt_state)
        _, m4 = jax.jit(step)(state_sh, batch)
    kl1, kl4 = float(m1["kl"]), float(m4["kl"])
    finite = bool(np.all(np.isfinite([float(m4["loss"]),
                                      float(m4["grad_norm"]), kl4])))
    close = abs(kl4 - kl1) <= FSDP_KL_ATOL + FSDP_KL_RTOL * abs(kl1)
    return finite and close, (
        f"mesh={dict(mesh.shape)} kl_one_chip={kl1!r} kl_mesh={kl4!r} "
        f"within rtol={FSDP_KL_RTOL},atol={FSDP_KL_ATOL}: {close} "
        f"finite={finite}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: kernels, serving and QAD on one chip; 4: TP "
                    "serving and FSDPxTP QAD against one chip")
    args = ap.parse_args(argv)
    devices = _require_chip(args.chips)

    import jax

    from repro.kernels import ops
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(jax.devices())}
    print(f"[smoke] device={dev} jax={jax.__version__} "
          f"interpret={ops.interpret_default()} compile-cache={cache_dir}",
          flush=True)
    clock = CompileClock()
    phases = ([("kernels", phase_kernels), ("serve", phase_serve),
               ("train", phase_train)] if args.chips == 1 else
              [("tp", phase_tp), ("fsdp_tp", phase_fsdp_tp)])
    failed = [name for name, fn in phases
              if not run_phase(name, fn, clock, devices)]
    if failed:
        print(f"[smoke] FAILED: {','.join(failed)}", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
