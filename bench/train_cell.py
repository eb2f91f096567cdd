"""A training cell: the program's jitted QAD step, closed loop.

Set-up builds one object, the compiled step with its state (weights made on
the device from the seed, the teacher a copy of the student, AdamW's
moments at zero), and drives it through its first three steps with the
window's own call and feed.  The program's readings are taken from those
steps: each step's loss, the norm of the first gradient as AdamW got it
(its first moment after step 1, per layer of each leaf), and the norm of
each layer's change after step 3 (student minus the untouched teacher).
The same object then runs back to back for the window, each batch made on
the host by the seeded generator as a loader would.  After the window the
state is freed and the plain reference follows the same three steps.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

import reference
import traffic
import weights

F32 = jnp.float32
STEPS_COMPARED = 3
# leaves whose reference gradient is below this share of the median
# layer's are moved by round-off alone and are left out of the change
NEGLIGIBLE_GRAD = 1e-3


def program_config(conf: dict, dims: dict):
    """The program's ModelConfig for a configuration file: the architecture
    preset it names, at the file's sizes."""
    from repro import configs

    return dataclasses.replace(
        configs.get_config(conf["repro_arch"]), n_layers=dims["n_layers"],
        d_model=dims["d_model"], n_heads=dims["n_heads"],
        n_kv_heads=dims["n_kv_heads"], d_head=dims["head_dim"],
        d_ff=dims["d_ff"], vocab_size=dims["vocab_size"],
        tie_embeddings=dims["tie_embeddings"], rope_theta=dims["rope_theta"],
        qkv_bias=True, norm="rmsnorm", mlp="swiglu")


def check_tree(params, model, cfg) -> None:
    """The benchmark's weights have the program's parameter shapes."""
    want = jax.eval_shape(
        lambda: model.init_params(cfg, jax.random.PRNGKey(0)))
    got = jax.tree.map(lambda x: (x.shape, x.dtype), params)
    want = jax.tree.map(lambda x: (x.shape, x.dtype), want)
    if got != want:
        raise SystemExit(f"[bench] weights do not match the program's "
                         f"parameters: {got} vs {want}")


def _units(tree):
    """Per-layer slices of the stacked leaves, and the other leaves whole,
    as [norm] float32 vectors in a fixed order."""
    out = []
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        x = x.astype(F32)
        if jax.tree_util.keystr(path).startswith("['layers']"):
            out.append(jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim)))))
        else:
            out.append(jnp.sqrt(jnp.sum(x * x))[None])
    return jnp.concatenate(out)


def unit_names(tree) -> list:
    names = []
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        k = jax.tree_util.keystr(path)
        names += ([f"{k}[{i}]" for i in range(x.shape[0])]
                  if k.startswith("['layers']") else [k])
    return names


norms = jax.jit(_units)
change_norms = jax.jit(lambda a, b: _units(jax.tree.map(
    lambda x, y: x.astype(F32) - y.astype(F32), a, b)))


def compare(prog: dict, ref: dict) -> dict:
    """The gaps between the program's readings and the reference's:

    * ``loss_gap``: the largest |loss - loss_ref| / |loss_ref| of the
      three steps; ``loss_mean_gap`` the same of the three steps' mean;
    * ``grad_gap``: over layers of leaves, the largest gap between the
      norms of the first gradient, over the larger of the reference's norm
      and the median layer's;
    * ``change_gap``: the same for each layer's change after step 3,
      leaving out layers whose reference gradient is negligible.
    """
    lp, lr = np.asarray(prog["loss"]), np.asarray(ref["loss"])
    gp, gr = np.asarray(prog["grad"]), np.asarray(ref["grad"])
    cp, cr = np.asarray(prog["change"]), np.asarray(ref["change"])
    med = np.median(gr)
    keep = gr >= NEGLIGIBLE_GRAD * med
    medc = np.median(cr[keep])
    gaps = {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "loss_mean_gap": float(abs(lp.mean() - lr.mean()) / abs(lr.mean())),
        "grad_gap": float(np.max(np.abs(gp - gr) / np.maximum(gr, med))),
        "change_gap": float(np.max(np.abs(cp - cr)[keep]
                                   / np.maximum(cr[keep], medc))),
    }
    return {k: (v if np.isfinite(v) else float("inf"))
            for k, v in gaps.items()}


@functools.lru_cache(maxsize=None)
def _reference_step(dims_items: tuple, opt, prec):
    return reference.make_qad_step(dict(dims_items), opt, prec)


@functools.lru_cache(maxsize=None)
def _program_step(cfg, lr: float):
    from repro.core import qad
    from repro.launch import specs
    from repro.optim import AdamW

    opt = AdamW(lr=lr, clip_norm=1.0)
    step = qad.make_train_step(get_model(cfg), cfg, specs.recipe_qconfig(cfg),
                               opt)
    return opt, jax.jit(step, donate_argnums=(0,))


def get_model(cfg):
    from repro.models import get_model as program_model

    return program_model(cfg)


def reference_readings(dims, lr, mix, rows, seed, prec, batch=None) -> dict:
    """The plain reference through the first three steps.  ``batch`` may
    replace the traffic's batch function (a planted fault)."""
    batch = batch or (lambda i: traffic.train_batch(
        mix, dims["vocab_size"], rows, seed, i))
    student, teacher = weights.build(dims, seed), weights.build(dims, seed)
    opt = reference.AdamW(lr=lr)
    state = opt.init(student)
    step = _reference_step(tuple(sorted(dims.items())), opt, prec)
    out = {"loss": []}
    for i in range(STEPS_COMPARED):
        student, state, loss = step(student, teacher, state, batch(i),
                                    jnp.float32(i))
        out["loss"].append(float(loss))
        if i == 0:
            out["grad"] = np.asarray(norms(state["m"]))
    out["change"] = np.asarray(change_norms(student, teacher))
    return out


class Program:
    """The program's QAD step and state for one seed."""

    def __init__(self, cell: dict, seed: int):
        from repro.core import qad

        conf, wl = cell["config"], cell["cell"]
        self.mix, self.seed, self.rows = cell["mix"], seed, wl["batch"]
        self.dims = weights.dims_of(conf)
        cfg = program_config(conf, self.dims)
        model = get_model(cfg)
        opt, self.step = _program_step(cfg, wl["lr"])
        t = time.monotonic()
        params = weights.build(self.dims, seed)
        check_tree(params, model, cfg)
        self.state = qad.TrainState(
            step=jnp.zeros((), jnp.int32), student=params,
            teacher=jax.tree.map(jnp.copy, params),
            opt_state=opt.init(params))
        jax.block_until_ready(self.state)
        self.weights_s = time.monotonic() - t
        self.i = 0

    def batch(self, i: int) -> dict:
        return traffic.train_batch(self.mix, self.dims["vocab_size"],
                                   self.rows, self.seed, i)

    def advance(self, annotate: bool = False):
        """One step on the next batch; returns its loss (on device).  The
        host's making of the batch is the ``bench.feed`` span."""
        with (jax.profiler.TraceAnnotation("bench.feed") if annotate
              else contextlib.nullcontext()):
            batch = jax.device_put(self.batch(self.i))
        self.state, m = self.step(self.state, batch)
        self.i += 1
        return m["loss"]

    def first_steps(self) -> dict:
        out = {"loss": []}
        for i in range(STEPS_COMPARED):
            out["loss"].append(float(self.advance()))
            if i == 0:
                out["grad"] = np.asarray(norms(self.state.opt_state.m))
        out["change"] = np.asarray(change_norms(self.state.student,
                                                self.state.teacher))
        return out

    def window(self, seconds: float, annotate: bool = False):
        """Back-to-back steps for ``seconds``, one in flight at a time;
        returns (steps, seconds from the first call to the last result,
        losses).  The window closes on the result of the step that
        crossed the time."""
        ctx = (jax.profiler.TraceAnnotation("bench.window") if annotate
               else contextlib.nullcontext())
        losses = []
        with ctx:
            t0 = time.monotonic()
            while True:
                losses.append(self.advance(annotate))
                if len(losses) > 1:
                    losses[-2].block_until_ready()
                if time.monotonic() - t0 >= seconds:
                    break
            losses[-1].block_until_ready()
            dt = time.monotonic() - t0
        return len(losses), dt, [float(x) for x in losses]

    def free(self) -> None:
        for x in jax.tree.leaves(self.state):
            x.delete()
        self.state = None


def run(cell: dict, seed: int, seconds: float, trace: bool, devices,
        clock, t_start: float, readers: dict, peaks: dict | None) -> dict:
    import harness

    wl, dims = cell["cell"], weights.dims_of(cell["config"])
    prog = Program(cell, seed)
    c0 = clock.snapshot()
    readings = prog.first_steps()
    setup_s = time.monotonic() - t_start
    c1 = clock.snapshot()
    tokens_per_step = prog.rows * cell["mix"]["seq_len"]
    if trace:
        with harness.Profile(cell["name"]) as prof:
            steps, dt, losses = prog.window(
                min(seconds, wl["trace_seconds"]), annotate=True)
    else:
        steps, dt, losses = prog.window(seconds)
    c2 = clock.snapshot()
    peak = harness.memory_peak(devices) if devices else 0
    prog.free()
    print(f"[bench] setup_s={setup_s:.3f} weights={prog.weights_s:.3f}s "
          f"compile={c1[0] - c0[0]:.3f}s (with the first steps) "
          f"cache_hits={c1[2]} programs_traced={c1[1]} "
          f"compiles_in_window={c2[1] - c1[1]} steps={steps} "
          f"window={dt:.3f}s", file=sys.stderr, flush=True)

    ref = reference_readings(dims, wl["lr"], cell["mix"], prog.rows, seed,
                             reference.Precision())
    gaps = compare(readings, ref)
    print(f"[bench] losses program={readings['loss']} "
          f"reference={ref['loss']}", file=sys.stderr, flush=True)
    checks = {k: {"value": gaps[k], "limit": v}
              for k, v in wl["limits"].items()}
    print("[bench] not compared: " + " ".join(
        f"{k}={v!r}" for k, v in gaps.items() if k not in checks),
        file=sys.stderr, flush=True)
    failed = sum(not np.isfinite(x) for x in losses)
    result = {"correct": all(c["value"] <= c["limit"]
                             for c in checks.values()) and not failed,
              "attempted": steps, "failed": failed, "checks": checks,
              "memory_peak_bytes": peak, "setup_s": setup_s,
              "compiles_in_window": c2[1] - c1[1]}
    if trace:
        import trace_events as te

        ev = prof.events
        win = te.spans(ev, "bench.window")
        lo, hi = win[0] if win else (0.0, 0.0)
        ctx = {"kind": "train", "events": ev, "window": (lo, hi),
               "dims": dims, "peaks": peaks, "tokens": steps * tokens_per_step,
               "seq_len": cell["mix"]["seq_len"]}
        result["metrics"] = {}
        for m in cell["per_layer"]:
            v = readers[m["name"]].read(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        result["busy_s"] = te.busy(ev, [(lo, hi)])
        result["window_s"] = (hi - lo) * 1e-9
        result["breakdown"] = {
            "device_ops": te.top_ops(te.inside(te.ops(ev), [(lo, hi)])),
            "idle_gaps": te.idle_gaps(ev, (lo, hi), {"bench.feed": "feed"})}
    else:
        values = {"train_tok_s": steps * tokens_per_step / dt,
                  "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell["end_to_end"]}
    return result
