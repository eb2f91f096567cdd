"""QAD / QAT step factories — the paper's contribution as a composable module.

``make_train_step(model, cfg, qcfg, opt, loss=...)`` builds a jit-able
``step(state, batch) -> (state, metrics)``:

  * **QAD** (``loss="kl"``): teacher = BF16 params (frozen), student = same
    architecture with NVFP4 fake-quant forward; loss = KL(p_t || p_s), T=1.
  * **QAT** (``loss="ce"``): student only, next-token cross entropy.
  * ablations: ``loss="mse"`` (logit MSE, Table 8) and ``loss="kl+ce"``.

One SPMD program evaluates teacher forward (no-grad — logits stop-gradient'd
so XLA keeps no teacher residuals), student forward + backward, and the
optimizer update.  Metrics include the paper's Table-1 diagnostics (KL vs
teacher AND CE vs labels) for every mode.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from . import losses
from .qconfig import QuantConfig, BF16
from ..obs import numerics as obs_numerics


class TrainState(NamedTuple):
    step: jax.Array
    student: Any                # trainable params (pytree)
    teacher: Any | None         # frozen BF16 params (None for pure QAT)
    opt_state: Any


@dataclasses.dataclass(frozen=True)
class QADConfig:
    loss: str = "kl"            # kl | ce | mse | kl+ce
    ce_weight: float = 0.1      # for kl+ce
    use_chunked_loss: bool = False
    loss_chunks: int = 16
    temperature: float = 1.0    # paper uses T=1 for exact distribution match


# The step's phases as ``jax.named_scope``s: every HLO instruction of the
# compiled step carries its scope in ``metadata.op_name`` (the student's
# backward as ``transpose(jvp(qad.student))``), so a device profile's op
# events join to a phase by instruction name.  Scopes are metadata only:
# the step computes bitwise what it computes without them.
PHASES = ("qad.teacher", "qad.student", "qad.kl", "qad.optimizer")
TEACHER, STUDENT, KL, OPTIMIZER = PHASES


def init_state(model, cfg, rng, opt, with_teacher: bool = True) -> TrainState:
    params = model.init_params(cfg, rng)
    teacher = jax.tree.map(jnp.copy, params) if with_teacher else None
    return TrainState(step=jnp.zeros((), jnp.int32), student=params,
                      teacher=teacher, opt_state=opt.init(params))


def make_loss_fn(model, cfg, qcfg: QuantConfig, qad: QADConfig):
    """Builds loss(student, teacher, batch) -> (loss, metrics)."""

    def loss_fn(student, teacher, batch):
        mask = batch["mask"].astype(jnp.float32)
        t = qad.temperature

        if qad.use_chunked_loss and qad.loss == "kl":
            with jax.named_scope(STUDENT):
                h_s = model.apply(cfg, student, batch, qcfg, output="hidden")
                w_s = model.unembed(cfg, student)
                # keep lm_head quantization parity with the plain path
                h_s = qcfg.q_act(h_s, "lm_head")
                w_s = qcfg.q_weight(w_s, "lm_head", contract_axis=0)
            with jax.named_scope(TEACHER):
                h_t = model.apply(cfg, teacher, batch, BF16, output="hidden")
                h_t = jax.lax.stop_gradient(h_t)
                w_t = jax.lax.stop_gradient(model.unembed(cfg, teacher))
            with jax.named_scope(KL):
                kl = losses.chunked_kl_loss(h_t, w_t, h_s, w_s, mask,
                                            qad.loss_chunks)
            return kl, {"kl": kl}

        # numerics probes (repro.obs.numerics): with qcfg.numerics on, a
        # local Tape collects per-layer quant-error stats from the
        # student forward and per-layer hiddens from BOTH forwards; the
        # drained values join the metrics aux as ordinary jit outputs.
        # The context managers run at trace time; numerics=False (the
        # default) takes the exact pre-probe path.
        tape = obs_numerics.Tape() if qcfg.numerics else None
        with jax.named_scope(STUDENT):
            if tape is not None:
                with obs_numerics.collecting(tape):
                    s_logits = model.apply(cfg, student, batch, qcfg)
                s_aux = tape.drain()
            else:
                s_logits = model.apply(cfg, student, batch, qcfg)
        metrics = {}
        with jax.named_scope(KL):
            ce = losses.ce_from_logits(s_logits, batch["labels"], mask)
        metrics["ce"] = ce

        if qad.loss == "ce":                       # QAT
            if tape is not None:
                metrics["numerics"] = _numerics_metrics(s_aux, None, mask)
            return ce, metrics

        with jax.named_scope(TEACHER):
            if tape is not None:
                t_qcfg = dataclasses.replace(BF16, numerics=True)
                with obs_numerics.collecting(tape):
                    t_logits = jax.lax.stop_gradient(
                        model.apply(cfg, teacher, batch, t_qcfg))
                t_aux = tape.drain()
            else:
                t_logits = jax.lax.stop_gradient(
                    model.apply(cfg, teacher, batch, BF16))
        with jax.named_scope(KL):
            kl = losses.kl_from_logits(t_logits / t, s_logits / t, mask)
            metrics["top1_agree"] = losses.top1_agreement(t_logits, s_logits,
                                                          mask)
        metrics["kl"] = kl
        if tape is not None:
            metrics["numerics"] = _numerics_metrics(s_aux, t_aux, mask)

        if qad.loss == "kl":                       # QAD
            return kl, metrics
        if qad.loss == "mse":                      # Table 8 ablation
            with jax.named_scope(KL):
                mse = losses.mse_from_logits(t_logits, s_logits, mask)
            metrics["mse"] = mse
            return mse, metrics
        if qad.loss == "kl+ce":
            return kl + qad.ce_weight * ce, metrics
        raise ValueError(qad.loss)

    return loss_fn


def _numerics_metrics(s_aux, t_aux, mask):
    """Shape drained probe tapes into the ``metrics["numerics"]`` aux.

    Raw per-layer hiddens (``layers.hidden``) from the two forwards are
    reduced to per-layer cosine/MSE here (the "internal geometry" view);
    every other student probe site (quant-error stats, incl. the
    ``layers.``-prefixed per-layer series from ``scan_layers``) passes
    through as ``{site: {stat: value}}``.  Everything is stop-gradient'd:
    probes observe training, they never steer it.
    """
    sg = jax.lax.stop_gradient
    out = {}
    h_s = s_aux.pop("layers.hidden", None)
    h_t = t_aux.pop("layers.hidden", None) if t_aux else None
    if h_s is not None and h_t is not None:
        out["layers.hidden"] = obs_numerics.hidden_divergence(
            sg(h_t["h"]), sg(h_s["h"]), mask)
    for site, stats in s_aux.items():
        out[site] = {k: sg(v) for k, v in stats.items()}
    return out


def make_train_step(model, cfg, qcfg: QuantConfig, opt,
                    qad: QADConfig | None = None) -> Callable:
    """The production train step (jit / pjit this)."""
    qad = qad or QADConfig()
    loss_fn = make_loss_fn(model, cfg, qcfg, qad)

    def step(state: TrainState, batch) -> tuple[TrainState, dict]:
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.student, state.teacher, batch)
        with jax.named_scope(OPTIMIZER):
            updates, opt_state = opt.update(grads, state.opt_state,
                                            state.student, state.step)
            student = jax.tree.map(lambda p, u: (p + u).astype(p.dtype),
                                   state.student, updates)
            metrics = dict(metrics, loss=loss,
                           grad_norm=_global_norm(grads),
                           update_norm=_global_norm(updates))
        if qcfg.numerics and isinstance(grads, dict) and "layers" in grads:
            # per-layer grad norm: every stacked-layer leaf carries the
            # [n_layers, ...] leading dim, so reduce all trailing axes
            sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)),
                             axis=tuple(range(1, g.ndim)))
                     for g in jax.tree.leaves(grads["layers"]))
            num = dict(metrics.get("numerics") or {})
            num["layers.grad"] = {"grad_norm": jnp.sqrt(sq)}
            metrics["numerics"] = num
        return TrainState(step=state.step + 1, student=student,
                          teacher=state.teacher, opt_state=opt_state), metrics

    return step


def make_eval_step(model, cfg, qcfg: QuantConfig,
                   qad: QADConfig | None = None) -> Callable:
    """Validation step: KL vs teacher + CE vs labels (paper Table 1)."""
    qad = qad or QADConfig()

    def eval_step(state: TrainState, batch) -> dict:
        mask = batch["mask"].astype(jnp.float32)
        s_logits = model.apply(cfg, state.student, batch, qcfg)
        out = {"ce": losses.ce_from_logits(s_logits, batch["labels"], mask)}
        if state.teacher is not None:
            t_logits = model.apply(cfg, state.teacher, batch, BF16)
            out["kl"] = losses.kl_from_logits(t_logits, s_logits, mask)
            out["top1_agree"] = losses.top1_agreement(t_logits, s_logits, mask)
        return out

    return eval_step


def _global_norm(tree) -> jax.Array:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32)))
              for x in jax.tree.leaves(tree)]
    return jnp.sqrt(sum(leaves)) if leaves else jnp.zeros(())
