"""QAD training driver — the end-to-end entry point.

CPU-runnable at reduced scale (``--smoke``), production-shaped otherwise:
auto-resume from the newest valid checkpoint, async saves, straggler
monitor, deterministic (step-indexed) data, Table-1-style eval (KL + CE).

    PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --smoke \
        --steps 200 --method qad
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp

from repro import configs
from repro.checkpoint import CheckpointManager
from repro.core import qad as qad_mod
from repro.core.qconfig import BF16
from repro.data import DataConfig, eval_batches, make_batch
from repro.distributed.fault import StragglerMonitor
from repro.launch import specs
from repro.models import get_model
from repro.optim import AdamW, warmup_cosine


def make_method_qad(method: str, lr: float):
    if method == "qad":
        return qad_mod.QADConfig(loss="kl")
    if method == "qat":
        return qad_mod.QADConfig(loss="ce")
    if method == "qad_mse":
        return qad_mod.QADConfig(loss="mse")
    if method == "qad_chunked":
        return qad_mod.QADConfig(loss="kl", use_chunked_loss=True)
    raise ValueError(method)


def train(arch: str, smoke: bool = True, steps: int = 200, lr: float = 1e-3,
          method: str = "qad", batch: int = 8, seq: int = 64,
          ckpt_dir: str | None = None, eval_every: int = 50,
          seed: int = 0, domains: tuple = ("math", "code", "prose"),
          numerics: bool = False, metrics_out: str | None = None,
          log=print):
    cfg = configs.get_smoke(arch) if smoke else configs.get_config(arch)
    model = get_model(cfg)
    qcfg = specs.recipe_qconfig(cfg)
    qadcfg = make_method_qad(method, lr)

    # --- numerics observability (repro.obs.numerics) -----------------------
    # ``numerics=True`` turns on the trace-time probe plane for the TRAIN
    # step only (per-layer SQNR / clip / scale-util, teacher-student hidden
    # divergence, per-layer grad norms ride out of jit as extra metrics —
    # the optimizer math is bitwise unchanged); the eval step stays
    # probe-free so its aggregation loop sees only scalars.  Snapshots
    # export per eval interval as ``repro.obs.metrics/v1`` documents.
    registry = recorder = None
    train_qcfg = qcfg
    if numerics:
        from repro.obs.metrics import MetricsRegistry
        from repro.obs.numerics import NumericsRecorder
        registry = MetricsRegistry()
        recorder = NumericsRecorder(registry)
        train_qcfg = dataclasses.replace(qcfg, numerics=True)

    opt = AdamW(lr=warmup_cosine(lr, steps // 10, steps), clip_norm=1.0)
    rng = jax.random.PRNGKey(seed)

    # teacher = "post-trained BF16 model": a fresh init here (benchmarks
    # pre-train it on the task first — see benchmarks/common.py)
    state = qad_mod.init_state(model, cfg, rng, opt,
                               with_teacher=(method != "qat_solo"))
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                      global_batch=batch, seed=seed, domains=domains)

    step_fn = jax.jit(
        qad_mod.make_train_step(model, cfg, train_qcfg, opt, qadcfg),
        donate_argnums=(0,))
    eval_fn = jax.jit(qad_mod.make_eval_step(model, cfg, qcfg, qadcfg))

    mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None
    start = 0
    if mgr is not None:
        restored = mgr.restore_latest(state)
        if restored is not None:
            start, state = restored
            log(f"[train] resumed from step {start}")

    mon = StragglerMonitor()
    history = []
    for i in range(start, steps):
        t0 = time.time()
        b = make_batch(dcfg, i)
        state, metrics = step_fn(state, b)
        dt = time.time() - t0
        action = mon.feed(dt)
        if action:
            log(f"[fault] straggler monitor: {action} at step {i}")
        if (i + 1) % eval_every == 0 or i == steps - 1:
            ev = [eval_fn(state, eb) for eb in eval_batches(dcfg, 2)]
            m = {k: float(jnp.mean(jnp.stack([e[k] for e in ev])))
                 for k in ev[0]}
            m["step"] = i + 1
            m["loss"] = float(metrics["loss"])
            m["grad_norm"] = float(metrics["grad_norm"])
            history.append(m)
            log(f"[train] step {i+1} " +
                " ".join(f"{k}={v:.4f}" for k, v in m.items() if k != "step"))
            if recorder is not None:
                recorder.record(metrics.get("numerics") or {})
                recorder.series_point("qad_train_kl", i + 1, m.get("kl"))
                recorder.series_point("qad_train_top1", i + 1,
                                      m.get("top1_agree"))
                if metrics_out:
                    from repro.obs import export as obs_export
                    obs_export.write_training_metrics(
                        metrics_out, i + 1, registry, recorder=recorder,
                        tokens=(i + 1) * batch * seq, evals=m)
                    log(f"[train] wrote {metrics_out} (+ .prom)")
            if mgr is not None:
                mgr.save(i + 1, state, metrics=m)
    if mgr is not None:
        mgr.wait()
    return state, history


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b", choices=configs.ALL_ARCHS)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--method", default="qad",
                    choices=["qad", "qat", "qad_mse", "qad_chunked"])
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--out", default=None)
    ap.add_argument("--numerics", action="store_true",
                    help="per-layer quantization-error + teacher-student "
                    "divergence probes on the train step (the optimizer "
                    "math is bitwise unchanged)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write a repro.obs.metrics/v1 snapshot here at "
                    "every eval interval (implies --numerics)")
    args = ap.parse_args()
    _, history = train(args.arch, args.smoke, args.steps, args.lr,
                       args.method, args.batch, args.seq, args.ckpt_dir,
                       numerics=args.numerics or bool(args.metrics_out),
                       metrics_out=args.metrics_out)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(history, f, indent=1)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
