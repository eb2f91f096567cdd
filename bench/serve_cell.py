"""A serving cell: the program's continuous-batching engine on packed NVFP4
weights, driven by the cell's traffic.

Set-up makes the weights on the device from the seed, packs them with the
program's PTQ, builds the engine as ``launch.serve.build_engine`` does,
warms every prompt length the mix draws (one short request each: the
prefill program per length, the decode step, the sampler), and then seats
the first wave: requests already in flight when the window opens, as many
as the cell's steady state holds, each with the rest of an output drawn
from the residual length distribution.

In the window requests arrive as the mix says (``traffic.py``), open loop
at the cell's fixed rate or closed loop with one client per slot, and
every token is stamped as ``Engine.step()`` returns it.  Time to first
token runs from a request's due time; the gap between tokens is taken
over every pair of a request's consecutive tokens in the window.  After
the window no request arrives; the engine runs on until every request due
in the window has its first token, for at most ``DRAIN_S`` seconds.  A
request still without one then has failed.

Correctness: a sample drawn from the seed of the requests that finished,
the one with the most served tokens among them, is run once through the
plain reference (``reference.py``) over its prompt and served tokens.  At
each served token the compared number is the gap by which the served
token's reference logit lies below the reference's best, in units of the
reference row's standard deviation; the widest gap over the sample is
held to the cell's limit.
"""
from __future__ import annotations

import contextlib
import dataclasses
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

import reference
import traffic
import weights
from train_cell import check_tree, program_config

DRAIN_S = 60.0
F32 = jnp.float32


class Server:
    """The engine under test for one seed, and the traffic it serves."""

    def __init__(self, cell: dict, seed: int, trace: bool):
        from repro.launch import specs
        from repro.models import get_model
        from repro.obs import Observability
        from repro.serve import Engine

        conf, wl = cell["config"], cell["cell"]
        self.cell, self.mix, self.seed = cell, cell["mix"], seed
        self.dims = weights.dims_of(conf)
        self.cfg = program_config(conf, self.dims)
        self.model = get_model(self.cfg)
        self.qcfg = dataclasses.replace(
            specs.recipe_qconfig(self.cfg), weight_format="packed",
            quantize_activations=self.dims["nvfp4_activations"])
        t = time.monotonic()
        packed = self.packed_weights(seed)
        self.weights_s = time.monotonic() - t
        bs, mb = wl["block_size"], wl["s_alloc"] // wl["block_size"]
        self.eng = Engine(
            self.cfg, packed, self.qcfg, n_slots=wl["slots"], block_size=bs,
            n_blocks=wl["pool_blocks"], max_blocks_per_slot=mb,
            prefill_mode=wl["prefill_mode"],
            fused_kernels=wl["fused_kernels"],
            obs=Observability(metrics=False, trace=True) if trace else None)
        self.reqs: dict = {}        # rid -> Request
        self.stamps: dict = {}      # rid -> [time of each token]
        self.due: dict = {}         # rid -> due time
        self.decode_contexts: list = []

    def packed_weights(self, seed: int):
        """The seed's weights, made on the device and packed to NVFP4 by
        the program's PTQ (as ``launch.serve.load_quantized`` does)."""
        from repro.core import ptq

        params = weights.build(self.dims, seed)
        check_tree(params, self.model, self.cfg)
        pspecs = self.model.param_specs(self.cfg)
        packed = jax.jit(lambda p: ptq.quantize_weights(p, pspecs, self.qcfg))(
            params)
        jax.block_until_ready(packed)
        for x in jax.tree.leaves(params):
            x.delete()
        return packed

    # -- driving the engine --------------------------------------------------

    def submit(self, req: dict, due: float) -> int:
        rid = self.eng.submit(req["prompt"], req["max_new"])
        self.reqs[rid] = self.eng.sched.waiting[-1]
        self.stamps[rid], self.due[rid] = [], due
        return rid

    def step(self, record: bool = False) -> list:
        before = {rid: len(r.output) for rid, r in self.reqs.items()
                  if r.finish_step < 0}
        finished = self.eng.step()
        now = time.monotonic()
        decoded = []
        for rid, n in before.items():
            r = self.reqs[rid]
            new = len(r.output) - n
            self.stamps[rid].extend([now] * new)
            if new > 1 or (new == 1 and n > 0):
                decoded.append(r.n_cached)
        if record and decoded:
            self.decode_contexts.append(decoded)
        return finished

    def warm(self) -> None:
        """Compile every shape the window uses: one request per prompt
        length of the mix, each with two tokens (prefill, decode,
        sampler at both batch sizes)."""
        rng = np.random.Generator(np.random.PCG64([self.seed, 4]))
        for p in self.mix["prompt_buckets"]:
            self.eng.submit(rng.integers(0, self.dims["vocab_size"], p,
                                         np.int32), 2)
        self.eng.drain(max_steps=10_000)

    def first_wave(self, n: int) -> None:
        """Seat ``n`` requests before the window, with outputs drawn from
        the residual length distribution, and prefill them all."""
        wave = traffic.first_wave(self.mix, self.dims["vocab_size"],
                                  self.seed, n)
        for req in wave:
            self.submit(req, due=-1.0)
        while self.eng.sched.waiting or any(
                r.state == "prefill" for r in self.eng.sched.in_flight()):
            self.step()

    def window(self, seconds: float, t0: float, record: bool,
               rate: float | None = None, drain: bool = True) -> dict:
        """Serve the window's traffic (open loop at ``rate``, the cell's
        by default); returns what the run measured."""
        wl, mix = self.cell["cell"], self.mix
        rate = rate or wl.get("rate")
        vocab = self.dims["vocab_size"]
        closed = mix["arrivals"] == "closed"
        due = (None if closed else
               traffic.arrivals(mix, rate, seconds, self.seed))
        n_max = wl["max_requests"] if closed else len(due)
        queue = traffic.requests(mix, vocab, self.seed, n_max)
        nxt, in_window = 0, []
        ctx = (jax.profiler.TraceAnnotation("bench.window") if record
               else contextlib.nullcontext())
        with ctx:
            while True:
                now = time.monotonic() - t0
                if now >= seconds:
                    break
                if closed:
                    while (nxt < n_max and len(self.eng.sched.waiting)
                           + len(self.eng.sched.in_flight()) < wl["slots"]):
                        in_window.append(self.submit(queue[nxt], now))
                        nxt += 1
                else:
                    while nxt < n_max and due[nxt] <= now:
                        in_window.append(self.submit(queue[nxt], due[nxt]))
                        nxt += 1
                if self.eng.sched.has_work():
                    self.step(record)
                elif not closed and nxt < n_max:
                    time.sleep(max(0.0, min(due[nxt], seconds) - now))
            t_close = time.monotonic()
        if closed and nxt >= n_max:
            raise SystemExit(f"[bench] max_requests={n_max} ran out inside "
                             "the window")
        while drain and time.monotonic() - t_close < DRAIN_S and any(
                not self.stamps[r] for r in in_window):
            self.step()
        return {"in_window": in_window, "t0": t0, "t_close": t_close}

    def free(self) -> None:
        state = self.eng.state
        for x in jax.tree.leaves((self.eng.params,
                                  getattr(state, "pool", None)
                                  and state.pool.data)):
            x.delete()
        self.eng = None


# ---------------------------------------------------------------------------
# measurement
# ---------------------------------------------------------------------------


def latencies(srv: Server, win: dict) -> dict:
    """TTFTs of the requests due in the window and every inter-token gap
    inside it (seconds)."""
    t0, t1 = win["t0"], win["t_close"]
    ttft = [srv.stamps[r][0] - (t0 + srv.due[r]) for r in win["in_window"]
            if srv.stamps[r]]
    itl = []
    for rid, st in srv.stamps.items():
        st = [t for t in st if t0 <= t <= t1]
        itl += list(np.diff(st))
    failed = sum(not srv.stamps[r] for r in win["in_window"])
    tokens = sum(sum(t0 <= t <= t1 for t in st)
                 for st in srv.stamps.values())
    return {"ttft": ttft, "itl": itl, "failed": failed,
            "attempted": len(win["in_window"]), "tokens": tokens,
            "seconds": t1 - t0}


def end_to_end(lat: dict) -> dict:
    out = {"out_tok_s": lat["tokens"] / lat["seconds"]}
    if lat["ttft"]:
        out["ttft_p90_ms"] = 1e3 * float(np.percentile(lat["ttft"], 90))
    if lat["itl"]:
        out["itl_p95_ms"] = 1e3 * float(np.percentile(lat["itl"], 95))
    return out


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------


def sample_finished(srv: Server, n: int) -> list:
    """rids of up to ``n`` finished requests, drawn from the seed, the one
    with the most served tokens always among them."""
    done = sorted(rid for rid, r in srv.reqs.items() if r.finish_step >= 0)
    if not done:
        return []
    longest = max(done, key=lambda r: len(srv.reqs[r].output))
    rest = [r for r in done if r != longest]
    rng = np.random.Generator(np.random.PCG64([srv.seed, 5]))
    pick = rng.permutation(len(rest))[: n - 1]
    return [longest] + [rest[i] for i in sorted(pick)]


@jax.jit
def _token_gaps(logits, served, valid):
    """Per position: (best - logit of the served token) / row std."""
    best = jnp.max(logits, -1)
    got = jnp.take_along_axis(logits, served[..., None], -1)[..., 0]
    std = jnp.std(logits, -1)
    return jnp.where(valid, (best - got) / std, 0.0)


def reference_gaps(dims, seed, cases, s_pad: int,
                   prec=reference.Precision(), ref_prec=None) -> list:
    """The gap at each served token of each case (prompt, served tokens),
    one float32 array per case.  Every case is padded to ``s_pad``
    positions, so one program serves all of them.
    With ``ref_prec`` set, the served tokens are the ones ``prec`` puts
    first at each position (the control), read in the ``ref_prec``
    reference's logits."""
    params = weights.build(dims, seed)
    acts = dims["nvfp4_activations"]
    fwd = jax.jit(lambda p, t, n: reference.logits(
        dims, p, t, True, n if acts else None, prec))
    ref = None if ref_prec is None else jax.jit(
        lambda p, t, n: reference.logits(dims, p, t, True,
                                         n if acts else None, ref_prec))
    out = []
    for prompt, served in cases:
        p, k = len(prompt), len(served)
        toks = np.zeros((1, s_pad), np.int32)
        toks[0, :p] = prompt
        toks[0, p:p + k] = served
        n = jnp.asarray([p], jnp.int32)
        lg = fwd(params, jnp.asarray(toks), n)
        # the token at p + i was chosen from the logits at p + i - 1
        tgt = np.zeros((1, s_pad), np.int32)
        valid = np.zeros((1, s_pad), bool)
        tgt[0, p - 1:p - 1 + k] = served
        valid[0, p - 1:p - 1 + k] = True
        if ref is not None:
            tgt = np.asarray(jnp.argmax(lg, -1)).astype(np.int32)
            lg = ref(params, jnp.asarray(toks), n)
        gaps = _token_gaps(lg, jnp.asarray(tgt), jnp.asarray(valid))
        out.append(np.asarray(gaps[0, p - 1:p - 1 + k]))
    for x in jax.tree.leaves(params):
        x.delete()
    return out


def run(cell: dict, seed: int, seconds: float, trace: bool, devices,
        clock, t_start: float, readers: dict, peaks: dict | None) -> dict:
    import harness

    wl = cell["cell"]
    srv = Server(cell, seed, trace)
    c0 = clock.snapshot()
    srv.warm()
    srv.first_wave(wl["first_wave"])
    setup_s = time.monotonic() - t_start
    c1 = clock.snapshot()
    window = min(seconds, wl["trace_seconds"]) if trace else seconds
    if trace:
        with harness.Profile(cell["name"]) as prof:
            win = srv.window(window, time.monotonic(), True)
    else:
        win = srv.window(window, time.monotonic(), False)
    c2 = clock.snapshot()
    lat = latencies(srv, win)
    peak = harness.memory_peak(devices) if devices else 0
    print(f"[bench] setup_s={setup_s:.3f} weights={srv.weights_s:.3f}s "
          f"compile={c1[0] - c0[0]:.3f}s cache_hits={c1[2]} "
          f"programs_traced={c1[1]} compiles_in_window={c2[1] - c1[1]} "
          f"requests_in_window={lat['attempted']} failed={lat['failed']} "
          f"tokens_in_window={lat['tokens']} window={lat['seconds']:.3f}s",
          file=sys.stderr, flush=True)

    picked = sample_finished(srv, wl["check_requests"])
    cases = [(srv.reqs[r].prompt, np.asarray(srv.reqs[r].output, np.int32))
             for r in picked]
    queue_waits = [srv.reqs[r].admit_t - (win["t0"] + srv.due[r])
                   for r in win["in_window"] if srv.reqs[r].admit_t]
    prefills = [(srv.reqs[r].admit_t, srv.reqs[r].prompt_len)
                for r in win["in_window"]
                if win["t0"] <= (srv.reqs[r].admit_t or -1) <= win["t_close"]]
    contexts = srv.decode_contexts
    srv.free()
    gaps = reference_gaps(srv.dims, seed, cases, wl["s_alloc"])
    widest = max((float(g.max()) for g in gaps), default=float("inf"))
    print(f"[bench] checked {len(cases)} requests, "
          f"{sum(len(c[1]) for c in cases)} served tokens; widest gap per "
          f"request (std): {[round(float(g.max()), 4) for g in gaps]}",
          file=sys.stderr, flush=True)
    checks = {"served_logit_gap": {
        "value": widest, "limit": wl["limits"]["served_logit_gap"]}}
    result = {"correct": bool(cases) and not lat["failed"] and all(
        c["value"] <= c["limit"] for c in checks.values()),
              "attempted": lat["attempted"], "failed": lat["failed"],
              "checks": checks, "memory_peak_bytes": peak,
              "setup_s": setup_s, "compiles_in_window": c2[1] - c1[1]}
    if trace:
        import trace_events as te

        ev = prof.events
        win_spans = te.spans(ev, "bench.window")
        lo, hi = win_spans[0] if win_spans else (0.0, 0.0)
        ctx = {"kind": "serve", "events": ev, "window": (lo, hi),
               "dims": srv.dims, "peaks": peaks, "slots": wl["slots"],
               "decode_contexts": contexts, "queue_waits": queue_waits,
               "prefills": prefills}
        result["metrics"] = {}
        for m in cell["per_layer"]:
            v = readers[m["name"]].read(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        result["busy_s"] = te.busy(ev, [(lo, hi)])
        result["window_s"] = (hi - lo) * 1e-9
        result["breakdown"] = {
            "device_ops": te.top_ops(te.inside(te.ops(ev), [(lo, hi)])),
            "idle_gaps": te.idle_gaps(ev, (lo, hi), {
                "engine.prefill": "prefill",
                "engine.decode_step": "decode step"})}
    else:
        values = dict(end_to_end(lat), setup_s=setup_s)
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell["end_to_end"]}
    return result
