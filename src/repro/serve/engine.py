"""The continuous-batching engine facade: ``submit`` / ``step`` / ``drain``.

One ``step()`` = (admission + prefill under a token budget) + one jitted
batched decode over the active slots.  Per-request cache/state lives behind
the per-layer state protocol (``repro.serve.state``): the config's state
plan (``models.registry.serve_state_plan``) picks the backend —

  * paged KV  — decoder-family archs: ``decoder.decode_step_paged`` over
    [n_slots, 1] tokens against the block-granular pool (compiled once),
  * state slabs — recurrent (RWKV6 / RG-LRU) and encoder-conditioned
    (Whisper) archs: the model's batched ``decode_step_slots`` over
    constant-size per-slot state at independent positions (compiled once).

Prefill is either "exact" mode (the model's ``prefill`` at the request's
own prompt length: bit-identical to the static ``serve_batch`` path,
compiled once per distinct prompt length; the cache lands in the backend
via ``write_prefill``) or "chunked" mode (paged-KV plans only:
``decoder.prefill_chunk_paged`` at a fixed chunk size, numerically
*approximate* because dynamic NVFP4 activation amaxes become
chunk-granular).  Sampling is ``sampling.sample_tokens`` (compiled once).

Requests are numerically independent: the engine serves with
``act_scope="row"`` activation scales (see ``core.qconfig``), per-request
positions / masks (and, for slab backends, per-leaf active-row merges), and
— for MoE archs — per-row ("local") expert dispatch, so a request's tokens
match a single-request static ``serve_batch`` run regardless of
co-scheduled traffic.
"""
from __future__ import annotations

import dataclasses
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.qconfig import BF16
from repro.distributed import ctx as shd_ctx
from repro.kernels import paged_attention
from repro.models import common, decoder
from repro.models.registry import get_model
from repro.obs import NOOP as OBS_NOOP
from repro.obs import dispatch as obs_dispatch
from repro.obs import numerics as obs_numerics
from repro.obs.trace import request_tid

from . import state as state_mod
from .sampling import SamplingParams, sample_tokens_seeded
from .scheduler import RUNNING, Request, Scheduler


class Engine:
    """Continuous-batching serving engine over protocol state.

    ``qcfg`` is the (recipe) quantization policy the weights were prepared
    with — e.g. the second return of ``launch.serve.load_quantized``; the
    engine derives the serving config from it (runtime weight fake-quant
    off, per-row activation scales).  Defaults cover smoke scale; size
    ``n_blocks`` / ``n_slots`` to the deployment.  For slab-state archs the
    block geometry only sets ``s_alloc = max_blocks_per_slot * block_size``,
    the dense-state allocation bound.

    ``mesh`` (with optional ``rules``, default ``tp_only``) turns on
    tensor-parallel serving: params are placed per the sharding rules
    (``PackedNVFP4`` codes/scales partition along their column-/row-parallel
    dim via ``sharding.resolve_packed``), the paged KV pool shards along KV
    heads, and every jitted step traces inside the (mesh, rules) context so
    the packed GEMMs dispatch to the ``shard_map``'d kernel and activations
    carry TP constraints.  The steps stay the same single jitted
    static-shape functions — TP only changes where the bytes live.
    """

    # one decode step emits one token per request from one logits row
    scores_per_token = True

    def __init__(self, cfg, params, qcfg=None, *, n_slots: int = 8,
                 block_size: int = 16, n_blocks: int = 48,
                 max_blocks_per_slot: int = 8,
                 prefill_mode: str = "exact", prefill_chunk: int = 8,
                 prefill_budget: int | None = None, eos_id: int | None = None,
                 mesh=None, rules=None, fused_kernels: str = "auto",
                 prefix_cache: bool = False, kv_alloc: str = "reserve",
                 headroom: int = 2, max_q_len: int = 1,
                 obs=None, shadow_teacher=None, shadow_rate: float = 0.0):
        # refuse unservable configs before touching params or quant policy
        plan = state_mod.check_supported(cfg)
        self.state_plan = plan
        self.paged = plan == ("paged_kv",)
        if prefill_mode not in ("exact", "chunked", "paged"):
            raise ValueError(prefill_mode)
        if prefill_mode in ("chunked", "paged") and not self.paged:
            raise ValueError(
                f"{prefill_mode} prefill requires the paged-KV state plan; "
                f"{cfg.name} plans {' + '.join(plan)}")
        if (prefix_cache or kv_alloc == "ondemand") \
                and prefill_mode != "paged":
            # sharing and preempt-resume both replay block-granular chunks
            # through the token-causal verify forward against the pool, so
            # block content is a pure function of its token prefix — the
            # exact/chunked prefill paths don't have that property
            raise ValueError(
                "prefix_cache / kv_alloc='ondemand' require "
                f"prefill_mode='paged' (got {prefill_mode!r})")
        if cfg.n_experts and cfg.moe_dispatch not in ("local", "token"):
            # per-row (or per-token) dispatch makes MoE routing independent
            # of co-batched requests — a hard requirement for continuous
            # batching
            cfg = dataclasses.replace(cfg, moe_dispatch="local")
        self.cfg = cfg
        self.model = get_model(cfg)
        self.mesh = mesh
        self.rules = rules
        if mesh is not None and rules is None:
            from repro.distributed import sharding as shd
            self.rules = shd.make_rules(mesh, "tp_only")
        if mesh is not None:
            params = self._shard(params, self.model.param_specs(cfg))
        self.params = params
        if qcfg is None:
            from repro.launch import specs
            qcfg = specs.recipe_qconfig(cfg)
        self.sq = dataclasses.replace(qcfg, quantize_weights=False,
                                      act_scope="row")

        # --- fused serving-kernel tier -------------------------------------
        # "on"/"off" force it; "auto" enables it when the fused kernels can
        # serve this config: paged-KV state plan (the fused attention kernel
        # streams pool pages), no mesh (pallas_call does not partition
        # under GSPMD — TP keeps the shard_map'd 2-D GEMM + gather attend)
        # and a page strip that fits the kernel's VMEM.  ``max_q_len`` is
        # the most queries one attention call scores (1 for decode; the
        # speculative engine passes k+1; paged prefill feeds whole blocks).
        if fused_kernels not in ("on", "off", "auto"):
            raise ValueError(f"fused_kernels={fused_kernels!r}: "
                             "expected 'on', 'off' or 'auto'")
        if fused_kernels == "on" and not self.paged:
            raise ValueError("fused_kernels='on' requires the paged-KV "
                             f"state plan; {cfg.name} plans "
                             f"{' + '.join(plan)}")
        if fused_kernels == "on" and mesh is not None:
            raise ValueError("fused_kernels='on' is single-device only; "
                             "drop the mesh or use 'auto'")
        fits = True
        if self.paged:
            q_len = max(max_q_len,
                        block_size if prefill_mode == "paged" else 1)
            strip = (max_blocks_per_slot * block_size, cfg.n_kv_heads,
                     cfg.head_dim, cfg.n_heads // cfg.n_kv_heads * q_len)
            fits = paged_attention.fits_vmem(*strip)
            if fused_kernels == "on" and not fits:
                need = paged_attention.vmem_bytes(*strip) / 2**20
                limit = paged_attention.SCOPED_VMEM_BYTES / 2**20
                raise ValueError(
                    f"fused_kernels='on': paged attention over {strip[0]} "
                    f"keys needs {need:.1f} MiB of VMEM, more than "
                    f"{limit:.0f} MiB; use 'auto' for the two-step")
        self.fused = (fused_kernels == "on"
                      or (fused_kernels == "auto" and self.paged
                          and mesh is None and fits))
        if self.fused and self.sq.packed_backend == "auto":
            # route 3-D packed MoE expert stacks through the grouped Pallas
            # GEMM instead of dequant-to-HBM + einsum
            self.sq = dataclasses.replace(self.sq, packed_backend="grouped")

        self.n_slots = n_slots
        self.max_blocks_per_slot = max_blocks_per_slot
        self.s_alloc = max_blocks_per_slot * block_size
        self.prefill_mode = prefill_mode
        self.prefill_chunk = prefill_chunk
        self.prefill_budget = prefill_budget or max(self.s_alloc,
                                                    prefill_chunk)
        self.eos_id = eos_id

        self.kv_alloc = kv_alloc
        self.prefix_cache = prefix_cache
        self.state = state_mod.make_state(
            self, cfg, n_slots=n_slots, block_size=block_size,
            n_blocks=n_blocks, max_blocks_per_slot=max_blocks_per_slot,
            s_alloc=self.s_alloc, kv_alloc=kv_alloc, headroom=headroom,
            prefix_cache=prefix_cache)
        self.pool = getattr(self.state, "pool", None)  # paged back-compat
        self.sched = Scheduler(self.state, n_slots, max_blocks_per_slot)
        self.scratch = None
        if prefill_mode == "chunked":
            sspecs = decoder.prefill_scratch_specs(cfg, self.s_alloc)
            self.scratch = self._shard(common.zeros_from_specs(sspecs),
                                       sspecs)
            self._chunk = jax.jit(
                lambda params, scratch, pool, bt, start, n_valid, toks:
                self._traced(decoder.prefill_chunk_paged, self.cfg, params,
                             scratch, pool, bt, start, n_valid,
                             {"tokens": toks}, self.sq),
                donate_argnums=(1, 2))
        if prefill_mode == "paged":
            # block-granular prompt replay through the token-scope verify
            # forward: every fed position writes its pool KV and attends
            # earlier POOL content, so each block's bytes are a pure
            # function of its token prefix — sequential-decode bitwise
            # semantics (see decoder.verify_step_paged), which is what
            # makes prefix-cache hits and preempt-resume recompute exact
            pcfg = dataclasses.replace(cfg, moe_dispatch="token") \
                if cfg.n_experts else cfg
            self.psq = dataclasses.replace(self.sq, act_scope="token")
            self._paged_chunk = jax.jit(
                lambda params, pool, bt, lens, active, n_prop, toks:
                self._traced(decoder.verify_step_paged, pcfg, params, pool,
                             bt, lens, active, n_prop, {"tokens": toks},
                             self.psq, fused=self.fused),
                donate_argnums=(1,))

        self._sample = jax.jit(sample_tokens_seeded)
        self._prefill_fns: dict[int, object] = {}

        self.step_count = 0
        self.decode_steps = 0
        self.tokens_generated = 0
        self.decode_tokens = 0
        self.prefill_tokens = 0
        self.decode_s = 0.0
        self.prefill_s = 0.0

        # --- telemetry (repro.obs) -----------------------------------------
        # Instrument handles are bound ONCE here; the hot path only calls
        # bound no-arg/one-arg methods.  Without an ``obs`` bundle every
        # handle is the shared no-op singleton — the engine allocates no
        # metric objects and the decode loop is unchanged.
        self.obs = obs if obs is not None else OBS_NOOP
        m = self.obs.metrics
        req_events = m.counter("serve_requests_total",
                               "request lifecycle events",
                               labels=("event",))
        self._m_req_submitted = req_events.labels(event="submitted")
        self._m_req_finished = {
            r: req_events.labels(event=f"finished_{r}")
            for r in ("eos", "length")}
        toks = m.counter("serve_tokens_total", "tokens processed per phase",
                         labels=("phase",))
        self._m_tok_prefill = toks.labels(phase="prefill")
        self._m_tok_decode = toks.labels(phase="decode")
        self._m_queue_depth = m.gauge("serve_queue_depth",
                                      "requests waiting for admission")
        self._m_active_slots = m.gauge("serve_active_slots",
                                       "slots occupied at the last decode")
        self._m_state_used = m.gauge(
            "serve_state_used",
            "state backend occupancy, used allocation units "
            "(blocks for paged KV, slots for slabs)")
        self._m_state_capacity = m.gauge(
            "serve_state_capacity", "state backend capacity, same unit")
        self._m_queue_wait = m.histogram("serve_queue_wait_seconds",
                                         "submit-to-admission wait")
        self._m_ttft = m.histogram("serve_ttft_seconds",
                                   "submit-to-first-token latency")
        self._m_itl = m.histogram("serve_inter_token_seconds",
                                  "per-request gap between emitted tokens")
        self._m_prefill_step = m.histogram(
            "serve_prefill_step_seconds",
            "wall time of one step's admission + prefill work")
        self._m_decode_step = m.histogram(
            "serve_decode_step_seconds",
            "wall time of one batched decode (or draft+verify) step")
        # prefix-cache + preemption plane (no-op singletons when obs is off
        # or the cache is disabled — counters simply never move)
        self._m_cache_hit = m.counter("prefix_cache_hit_total",
                                      "prefix-cache block hits at admission")
        self._m_cache_miss = m.counter(
            "prefix_cache_miss_total",
            "full prompt blocks that had to be recomputed")
        self._m_cache_evict = m.counter(
            "prefix_cache_evict_total",
            "cached blocks reclaimed under pool pressure")
        self._m_preempt = m.counter(
            "serve_preempt_total",
            "running requests evicted for pool pressure")
        self._m_requeue = m.counter(
            "serve_requeue_total",
            "preempted requests placed back at the queue front")
        self._m_shared_blocks = m.gauge(
            "serve_shared_blocks",
            "pool blocks referenced by more than one request")
        self._m_cached_blocks = m.gauge(
            "serve_cached_blocks",
            "unreferenced pool blocks retained by the prefix cache")
        self._m_compiles = m.counter(
            "jit_compiles_total",
            "watched jitted calls during which jax traced a program, i.e. "
            "compiled a new specialization", labels=("fn",))
        self._cache_seen = (0, 0)      # (hits, misses) already counted
        self.preempts = 0
        self._m_state_capacity.set(self.state.occupancy()[1])

        # --- numerics shadow-teacher (repro.obs.numerics) ------------------
        # Opt-in live divergence probe: on a deterministically sampled
        # fraction of decode steps, re-forward each running request's FULL
        # context through the BF16 teacher AND the quantized student
        # (stateless — never touches the serving caches, so token streams
        # are identical with the shadow on or off) and record per-request
        # KL / top-1 agreement plus per-layer hidden-state divergence and
        # quantization-error stats.  Cost is O(context) per sampled step.
        self.shadow_teacher = shadow_teacher
        self.shadow_rate = float(shadow_rate)
        self.shadow_steps = 0
        self.shadow_s = 0.0
        self.numerics = None
        self._shadow_fn = None
        if shadow_teacher is not None and self.shadow_rate > 0.0:
            self._shadow_every = max(1, round(1.0 / self.shadow_rate))
            self.numerics = obs_numerics.NumericsRecorder(self.obs.metrics)
            self._shadow_fn = self._build_shadow()

        # recompile tripwire: jax.monitoring's trace events during a watched
        # call mean jit compiled a new specialization (see _compile_watch)
        self._recompile_warned = False
        self._steady_after = 4          # decode steps before warning

    # -- TP plumbing -------------------------------------------------------

    def _traced(self, fn, *args, **kw):
        """Run a step builder inside the TP (mesh, rules) context.

        The context must be live at TRACE time (first jitted call), not at
        jit construction — entering it inside the traced function covers
        both, and is a no-op without a mesh.
        """
        with shd_ctx.maybe_use(self.mesh, self.rules):
            return fn(*args, **kw)

    def _shard(self, tree, specs):
        """device_put a spec-described tree per the TP rules (identity
        without a mesh)."""
        if self.mesh is None:
            return tree
        from repro.distributed import sharding as shd
        return shd.shard_params(tree, specs, self.mesh, self.rules)

    # -- public API --------------------------------------------------------

    def submit(self, prompt, max_new_tokens: int,
               sampling: SamplingParams | None = None,
               extras: dict | None = None, forced=None,
               keep_logits: bool = False) -> int:
        """Queue a request; returns its id.  Admission happens in step().

        ``extras`` carries non-token prefill inputs (unbatched; the engine
        adds the batch dim) — e.g. ``{"enc_frames": [T, n_mels]}`` for
        encoder-decoder archs.  ``forced`` (at least ``max_new_tokens``
        ids) teacher-forces the request: it emits these tokens instead of
        sampling, so its logits score a given continuation.
        ``keep_logits`` keeps, in ``logits(rid)``, the logits row each
        emitted token was chosen from.
        """
        if (forced is not None or keep_logits) and not self.scores_per_token:
            raise NotImplementedError(
                f"{type(self).__name__} scores draft chunks, not one token "
                "per step: forced / keep_logits need the plain Engine")
        if forced is not None and len(forced) < max_new_tokens:
            raise ValueError(f"forced holds {len(forced)} tokens, fewer "
                             f"than max_new_tokens={max_new_tokens}")
        req = self.sched.submit(prompt, max_new_tokens, sampling,
                                step=self.step_count, extras=extras)
        if forced is not None:
            req.forced = np.asarray(forced, np.int32)
        if keep_logits:
            req.logits = []
        req.submit_t = time.monotonic()
        req.submit_wall_t = time.time()     # the one wall-clock anchor
        self._m_req_submitted.inc()
        self._m_queue_depth.set(len(self.sched.waiting))
        tr = self.obs.trace
        if tr.enabled:
            tid = request_tid(req.rid)
            tr.thread_name(tid, f"request {req.rid}")
            tr.begin("request", tid, rid=req.rid,
                     prompt_len=req.prompt_len,
                     max_new_tokens=max_new_tokens,
                     submit_wall_t=req.submit_wall_t)
            tr.begin("queue", tid)
        return req.rid

    def step(self) -> list[Request]:
        """Advance the engine by one scheduling round.

        Admits + prefills queued requests under ``prefill_budget`` tokens,
        then runs one batched decode step for all running slots.  Returns
        the requests that finished during this step.
        """
        # install the dispatch recorder for the step's dynamic extent so
        # first-trace qeinsum/kernel dispatches are attributed to this
        # engine (compiled replays never reach the recorder — see
        # repro.obs.dispatch)
        with self.obs.trace.annotate("engine.step",
                                     n_active=len(self.sched.running())):
            if self.obs.dispatch is None:
                return self._step_impl()
            with obs_dispatch.recording(self.obs.dispatch):
                return self._step_impl()

    def _step_impl(self) -> list[Request]:
        finished: list[Request] = []
        self._do_prefills(finished)
        reqs = self.sched.running() if self.numerics is not None else ()
        self._do_decode(finished)
        if reqs and self.decode_steps % self._shadow_every == 0:
            self._run_shadow(reqs)
        self.step_count += 1
        return finished

    def drain(self, max_steps: int | None = None) -> dict[int, np.ndarray]:
        """Run ``step()`` until no request is waiting or in flight."""
        steps = 0
        while self.sched.has_work():
            if max_steps is not None and steps >= max_steps:
                raise RuntimeError(f"drain exceeded {max_steps} steps")
            self.step()
            steps += 1
        return self.outputs()

    def logits(self, rid: int) -> np.ndarray:
        """[n_emitted, V] fp32 logits of a finished request submitted
        with ``keep_logits``: row i is what its token i was chosen from."""
        rows = self.sched.finished[rid].logits
        return np.asarray(jnp.stack(rows), np.float32)

    def outputs(self) -> dict[int, np.ndarray]:
        return {rid: np.asarray(r.output, np.int32)
                for rid, r in self.sched.finished.items()}

    def stats(self) -> dict:
        d = {"steps": self.step_count, "decode_steps": self.decode_steps,
             "fused_kernels": self.fused,
             "packed_backend": self.sq.packed_backend,
             # unified schema with SpecEngine.stats(): plain decode reports
             # the speculative keys as disabled/None so exporters and
             # dashboards read one shape for both engines
             "speculative": False,
             "acceptance_rate": None,
             "accepted_per_step": None,
             "requests_finished": len(self.sched.finished),
             "preempts": self.preempts,
             "tokens_generated": self.tokens_generated,
             "prefill_tokens": self.prefill_tokens,
             "prefill_s": self.prefill_s, "decode_s": self.decode_s,
             "decode_tok_s": self.decode_tokens / max(self.decode_s, 1e-9),
             "e2e_tok_s": self.tokens_generated
             / max(self.decode_s + self.prefill_s, 1e-9)}
        d.update(self._latency_stats())
        d.update(self.state.stats())
        return d

    def token_gaps(self) -> list[float]:
        """Seconds between consecutive tokens of each finished request, from
        the stamps ``_emit`` puts on ``Request.token_t``."""
        return [b - a for r in self.sched.finished.values()
                for a, b in zip(r.token_t, r.token_t[1:])]

    def _latency_stats(self) -> dict:
        """Per-request TTFT and inter-token gap percentiles.

        Empty populations report ``None`` (not 0.0) — "no data" and "zero
        latency" are different answers and exporters render them apart.
        """
        ttfts = [r.ttft_s for r in self.sched.finished.values()
                 if r.first_tok_t]
        out = {}
        for name, vals in (("ttft", ttfts), ("decode_lat", self.token_gaps())):
            out[f"{name}_p50_s"] = float(np.percentile(vals, 50)) \
                if vals else None
            out[f"{name}_p95_s"] = float(np.percentile(vals, 95)) \
                if vals else None
        return out

    # -- prefill -----------------------------------------------------------

    def _do_prefills(self, finished: list[Request]) -> None:
        budget = self.prefill_budget
        t0 = time.monotonic()
        any_work = False
        while budget > 0:
            req = self._in_flight_prefill()
            if req is None:
                req = self._admit_next()
            if req is None:
                break
            any_work = True
            resumed = bool(req.output)     # re-admitted after preemption
            with self.obs.trace.annotate("engine.prefill", rid=req.rid):
                if self.prefill_mode == "exact":
                    if req.prompt_len > budget \
                            and budget < self.prefill_budget:
                        break              # defer to next step; never livelock
                    logits = self._prefill_exact(req)
                    used = req.prompt_len
                elif self.prefill_mode == "chunked":
                    logits, used = self._prefill_chunked(req, budget)
                else:
                    logits, used = self._prefill_paged(req, budget)
            budget -= used
            self.prefill_tokens += used
            self._m_tok_prefill.inc(used)
            if logits is None:
                break                      # budget ran out mid-prompt
            if self.prefill_mode == "paged":
                # make this context's full blocks shareable (also re-hits
                # this request's own blocks after a future preemption)
                self.state.register_prefix(req, req.resume_tokens())
            self._after_prefill(req)
            if self.obs.trace.enabled:
                self.obs.trace.end("prefill", request_tid(req.rid))
            if resumed:
                # the resume prefill only rebuilds KV over tokens already
                # emitted; its logits re-predict output[-1], which decode
                # re-feeds — emitting here would duplicate a token
                req.state = RUNNING
                if self.obs.trace.enabled:
                    self.obs.trace.begin("decode", request_tid(req.rid))
            else:
                with self.obs.trace.annotate("engine.first_token",
                                             rid=req.rid):
                    self._emit(req, self._sample_one(req, logits), finished)
        dt = time.monotonic() - t0
        self.prefill_s += dt
        if any_work:
            self._m_prefill_step.observe(dt)

    def _admit_next(self) -> Request | None:
        """Admit the queue head when a slot is free, under an
        ``engine.admit`` span (slot choice, state reservation and the
        admission hooks) and, when the prefix cache is live, a nested
        ``cache_lookup`` span (admission is where the cache walk and hit
        acquisition happen, inside ``state.reserve``)."""
        if not self.sched.waiting or self.sched.free_slot() is None:
            return None
        tr = self.obs.trace
        head = self.sched.waiting[0]
        with tr.annotate("engine.admit", rid=head.rid):
            if self.prefix_cache:
                with tr.annotate("cache_lookup", rid=head.rid):
                    req = self.sched.admit_next()
            else:
                req = self.sched.admit_next()
            if req is not None:
                self._on_admit(req)
        return req

    def _count_cache_evict(self, n: int) -> None:
        """State-backend hook: ``n`` cached blocks were just reclaimed."""
        if n:
            self._m_cache_evict.inc(n)

    def _sync_cache_counters(self) -> None:
        c = getattr(self.state, "cache", None)
        if c is None:
            return
        h0, m0 = self._cache_seen
        if c.hits > h0:
            self._m_cache_hit.inc(c.hits - h0)
        if c.misses > m0:
            self._m_cache_miss.inc(c.misses - m0)
        self._cache_seen = (c.hits, c.misses)

    def _on_admit(self, req: Request) -> None:
        """A request left the queue for a slot (state reserved)."""
        self._sync_cache_counters()
        self._m_queue_depth.set(len(self.sched.waiting))
        self._m_queue_wait.observe(req.queue_wait_s)
        tr = self.obs.trace
        if tr.enabled:
            tid = request_tid(req.rid)
            tr.end("queue", tid, slot=req.slot,
                   queue_wait_s=req.queue_wait_s)
            tr.begin("prefill", tid, prompt_len=req.prompt_len)

    def _after_prefill(self, req: Request) -> None:
        """Hook: a request's prompt is fully prefilled (state written), its
        first token not yet sampled.  The speculative engine prefills the
        draft model's mirrored state here."""

    def _in_flight_prefill(self) -> Request | None:
        """An admitted request whose prefill hasn't completed (chunked mode
        mid-prompt, or an exact-mode admission deferred by the budget)."""
        for r in self.sched.in_flight():
            if r.state == "prefill":
                return r
        return None

    def prefill_batch(self, req: Request) -> dict:
        """The model-facing prefill batch for one request (tokens + any
        extras, batch dim added)."""
        batch = {"tokens": jnp.asarray(req.prompt[None])}
        for k, v in (req.extras or {}).items():
            batch[k] = jnp.asarray(v)[None]
        return batch

    def _prefill_exact(self, req: Request) -> jax.Array:
        p = req.prompt_len
        if p not in self._prefill_fns:
            self._prefill_fns[p] = jax.jit(
                lambda params, batch: self._traced(
                    self.model.prefill, self.cfg, params, batch, self.sq,
                    None))
        logits, cache = self._prefill_fns[p](self.params,
                                             self.prefill_batch(req))
        cache = {k: v for k, v in cache.items() if k != "pos"}
        self.state.write_prefill(req, cache)
        req.n_prefilled = req.n_cached = req.n_written = p
        return logits[:, -1, :]

    def _prefill_chunked(self, req: Request, budget: int):
        """Advance chunked prefill by up to ``budget`` tokens; returns
        (last-position logits [1, V] | None, tokens consumed)."""
        c = self.prefill_chunk
        consumed, logits = 0, None
        bt = np.zeros((self.max_blocks_per_slot,), np.int32)
        bt[: len(req.block_ids)] = req.block_ids
        bt = jnp.asarray(bt)
        while req.n_prefilled < req.prompt_len and consumed < budget:
            n_valid = min(c, req.prompt_len - req.n_prefilled)
            toks = np.zeros((1, c), np.int32)
            toks[0, :n_valid] = req.prompt[req.n_prefilled:
                                           req.n_prefilled + n_valid]
            lg, self.scratch, self.pool.data = self._chunk(
                self.params, self.scratch, self.pool.data, bt,
                jnp.asarray(req.n_prefilled, jnp.int32),
                jnp.asarray(n_valid, jnp.int32), jnp.asarray(toks))
            req.n_prefilled += n_valid
            req.n_cached = req.n_written = req.n_prefilled
            consumed += n_valid
            if req.n_prefilled >= req.prompt_len:
                logits = lg[:, -1, :]
        return logits, consumed

    def _prefill_paged(self, req: Request, budget: int):
        """Advance block-granular paged prefill by up to ``budget`` tokens.

        The context (prompt, or prompt + emitted tokens after preemption)
        replays as block-size chunks through the token-scope verify
        forward, attending and writing the pool itself; prefix-cache hit
        blocks acquired at admission are skipped outright.  Returns
        (last-position logits [1, V] | None, tokens consumed).
        """
        bs = self.state.pool.block_size
        ctx = req.resume_tokens()
        n_ctx = len(ctx)
        if req.n_prefilled == 0 and req.n_cache_hit:
            # hit blocks already hold exactly the bytes this prefill would
            # write (block content is a pure function of its token prefix)
            req.n_prefilled = req.n_cached = req.n_written = req.n_cache_hit
        consumed, logits = 0, None
        bt = np.zeros((1, self.max_blocks_per_slot), np.int32)
        bt[0, : len(req.block_ids)] = req.block_ids
        bt = jnp.asarray(bt)
        while req.n_prefilled < n_ctx and consumed < budget:
            n_valid = min(bs, n_ctx - req.n_prefilled)
            toks = np.zeros((1, bs), np.int32)
            toks[0, :n_valid] = ctx[req.n_prefilled:
                                    req.n_prefilled + n_valid]
            lg, self.pool.data = self._paged_chunk(
                self.params, self.pool.data, bt,
                jnp.asarray([req.n_prefilled], jnp.int32),
                jnp.asarray([True]),
                jnp.asarray([n_valid - 1], jnp.int32),
                jnp.asarray(toks))
            req.n_prefilled += n_valid
            req.n_cached = req.n_written = req.n_prefilled
            consumed += n_valid
            if req.n_prefilled >= n_ctx:
                logits = lg[:, n_valid - 1, :]
        return logits, consumed

    # -- preemption (on-demand paging) -------------------------------------

    def _preempt_one(self, victim: Request) -> None:
        """Evict one running request: release its state, count it, and
        re-queue it at the front (``preempt`` + ``requeue`` spans on the
        engine thread, queue re-opened on the request thread)."""
        tr = self.obs.trace
        with tr.annotate("preempt", rid=victim.rid,
                         progress=len(victim.output)):
            if tr.enabled:
                tid = request_tid(victim.rid)
                tr.end("decode", tid)
                tr.begin("queue", tid)
            self.sched.preempt(victim)
        with tr.annotate("requeue", rid=victim.rid,
                         queue_depth=len(self.sched.waiting)):
            self.preempts += 1
            self._m_preempt.inc()
            self._m_requeue.inc()
            self._m_queue_depth.set(len(self.sched.waiting))

    def _ensure_decode_capacity(self, reqs: list[Request],
                                extra: int = 0) -> list[Request]:
        """On-demand mode: grow every running request's block table to
        cover its next KV write, evicting unreferenced cache blocks first
        and preempting the lowest-progress running request when the pool
        is truly full.  The requester itself can be its own victim, so one
        request always makes forward progress and saturation never
        deadlocks.  ``extra`` asks for best-effort additional room
        (speculative draft depth) that never triggers preemption.
        Returns the requests still in the round.
        """
        if self.kv_alloc != "ondemand":
            return reqs
        live = list(reqs)
        with self.obs.trace.annotate("engine.decode.grow"):
            for r in list(live):
                while r in live and not self.state.grow_to(r,
                                                           r.n_cached + 1):
                    victim = self.sched.preempt_victim()
                    assert victim is not None, \
                        "no preemption victim while growing"
                    self._preempt_one(victim)
                    if victim in live:
                        live.remove(victim)
            if extra:
                for r in live:
                    self.state.grow_to(r, r.n_cached + 1 + extra)
        return live

    # -- decode ------------------------------------------------------------

    def _do_decode(self, finished: list[Request]) -> None:
        """One batched decode over the running slots: inside the
        ``engine.decode_step`` span build the host inputs, enqueue the
        jitted step and the sampler and wait for the sampled tokens; then
        emit them under ``engine.decode.emit``.  Emitting stays outside
        the step's span, where it has always been, so the span measures
        what it measured before it had children."""
        reqs = self.sched.running()
        if reqs:
            reqs = self._ensure_decode_capacity(reqs)
        if not reqs:
            return
        tr = self.obs.trace
        t0 = time.monotonic()
        with tr.annotate("engine.decode_step", n_active=len(reqs)):
            with tr.annotate("engine.decode.inputs"):
                ns = self.n_slots
                toks = np.zeros((ns, 1), np.int32)
                lens = np.zeros((ns,), np.int32)
                active = np.zeros((ns,), bool)
                temps = np.zeros((ns,), np.float32)
                topks = np.zeros((ns,), np.int32)
                seeds = np.zeros((ns,), np.int32)
                idxs = np.zeros((ns,), np.int32)
                for r in reqs:
                    s = r.slot
                    toks[s, 0] = r.next_input_token()
                    lens[s] = r.n_cached
                    active[s] = True
                    temps[s] = r.sampling.temperature
                    topks[s] = r.sampling.top_k
                    seeds[s] = r.sampling.seed
                    idxs[s] = len(r.output)
                bt = self.state.block_table(reqs)
            with tr.annotate("engine.decode.dispatch"):
                logits = self._compile_watch(
                    "decode",
                    lambda: self.state.decode(reqs, toks, lens, active, bt))
            with tr.annotate("engine.decode.sample"):
                sampled = self._compile_watch(
                    "sample", lambda: self._sample(
                        logits[:, 0, :], jnp.asarray(temps),
                        jnp.asarray(topks), jnp.asarray(seeds),
                        jnp.asarray(idxs)))
            with tr.annotate("engine.decode.wait"):
                sampled = np.asarray(sampled)
        dt = time.monotonic() - t0
        self._note_decode_step(dt, len(reqs))
        self.decode_tokens += len(reqs)
        self._m_tok_decode.inc(len(reqs))
        with tr.annotate("engine.decode.emit", n_tokens=len(reqs)):
            for r in reqs:
                r.n_cached += 1
                r.n_written = max(r.n_written, r.n_cached)
                self._emit(r, self._chosen(r, int(sampled[r.slot]),
                                           logits[r.slot, 0]), finished)

    # -- shared ------------------------------------------------------------

    def _note_decode_step(self, dt: float, n_active: int) -> None:
        """Account one batched decode (or draft+verify) step's wall time and
        refresh the occupancy gauges.  Shared with the speculative engine so
        both report through the same instruments."""
        self.decode_s += dt
        self.decode_steps += 1
        self._m_decode_step.observe(dt)
        if self.obs.metrics.enabled:
            self._m_active_slots.set(n_active)
            used, cap = self.state.occupancy()
            self._m_state_used.set(used)
            self._m_state_capacity.set(cap)
            pool = self.pool
            if pool is not None:
                self._m_shared_blocks.set(pool.shared_blocks)
                self._m_cached_blocks.set(pool.cached_blocks)

    def _compile_watch(self, fn_name: str, thunk):
        """Run ``thunk`` watching for a (re)compile of its jitted call.

        jax traces a program only to compile a new specialization, so a
        trace event during the call (``obs_dispatch.programs_traced``)
        means it compiled: count it under ``jit_compiles_total{fn=...}``
        and — once, past warmup — warn that the steady-state loop is
        retracing (a shape or dtype leak into a traced argument, the
        classic silent perf cliff).
        """
        before = obs_dispatch.programs_traced()
        out = thunk()
        if obs_dispatch.programs_traced() > before:
            self._m_compiles.labels(fn=fn_name).inc()
            if self.decode_steps >= self._steady_after \
                    and not self._recompile_warned:
                self._recompile_warned = True
                print(f"[repro.obs] warning: {fn_name!r} recompiled at "
                      f"decode step {self.decode_steps} — a steady-state "
                      "engine loop should replay one compiled "
                      "specialization (check for shape/dtype churn in "
                      "traced arguments)", file=sys.stderr)
        return out

    # -- numerics shadow-teacher -------------------------------------------

    def _live_acceptance(self):
        """Speculative acceptance so far, or None (plain engine / no
        drafts).  The shadow probe cross-plots this against live KL."""
        return None

    def _build_shadow(self):
        """One jitted shadow evaluator (retraces per context bucket).

        Teacher = BF16 forward of ``shadow_teacher`` params; student = the
        serving quantization policy over the engine's (packed) params.
        Both run with ``numerics=True`` under local Tapes, so the drained
        aux rides out of jit as ordinary outputs — per-layer hidden taps
        from both sides feed ``hidden_divergence``, the student's
        quant-error probes pass through, and the last valid position
        yields KL(teacher || student) and top-1 agreement.
        """
        t_qc = dataclasses.replace(BF16, numerics=True)
        s_qc = dataclasses.replace(self.sq, numerics=True)

        def fn(t_params, s_params, batch, n_valid):
            t_tape = obs_numerics.Tape()
            with obs_numerics.collecting(t_tape):
                t_logits = self.model.apply(self.cfg, t_params, batch, t_qc)
            t_aux = t_tape.drain()
            s_tape = obs_numerics.Tape()
            with obs_numerics.collecting(s_tape):
                s_logits = self.model.apply(self.cfg, s_params, batch, s_qc)
            s_aux = s_tape.drain()
            tl = t_logits[0, n_valid - 1].astype(jnp.float32)
            sl = s_logits[0, n_valid - 1].astype(jnp.float32)
            tlp = jax.nn.log_softmax(tl)
            slp = jax.nn.log_softmax(sl)
            out = {"shadow": {
                "kl": jnp.sum(jnp.exp(tlp) * (tlp - slp)),
                "top1_agree": (jnp.argmax(tl) == jnp.argmax(sl))
                .astype(jnp.float32)}}
            h_t = t_aux.pop("layers.hidden", None)
            h_s = s_aux.pop("layers.hidden", None)
            if h_t is not None and h_s is not None:
                seq = batch["tokens"].shape[1]
                mask = (jnp.arange(seq)[None, :] < n_valid) \
                    .astype(jnp.float32)
                out["layers.hidden"] = obs_numerics.hidden_divergence(
                    h_t["h"], h_s["h"], mask)
            out.update(s_aux)
            return out

        return jax.jit(lambda tp, sp, b, nv: self._traced(fn, tp, sp, b, nv))

    def _run_shadow(self, reqs) -> None:
        """Score each request's full context teacher-vs-student (stateless;
        the serving caches and token streams are untouched).  Contexts pad
        to power-of-two buckets so compilations stay bounded."""
        t0 = time.monotonic()
        self.shadow_steps += 1
        kls, agrees = [], []
        for r in reqs:
            ctx = np.concatenate([np.asarray(r.prompt, np.int32),
                                  np.asarray(r.output, np.int32)])
            n = len(ctx)
            bucket = max(16, 1 << (n - 1).bit_length())
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :n] = ctx
            batch = {"tokens": jnp.asarray(toks)}
            for k, v in (r.extras or {}).items():
                batch[k] = jnp.asarray(v)[None]
            aux = jax.device_get(self._shadow_fn(
                self.shadow_teacher, self.params, batch,
                jnp.asarray(n, jnp.int32)))
            sh = aux.pop("shadow")
            kls.append(float(sh["kl"]))
            agrees.append(float(sh["top1_agree"]))
            self.numerics.record(aux)
        step = self.decode_steps
        self.numerics.record({"shadow": {
            "kl": float(np.mean(kls)),
            "top1_agree": float(np.mean(agrees))}})
        self.numerics.series_point("qad_live_kl", step, float(np.mean(kls)))
        self.numerics.series_point("qad_top1_agree", step,
                                   float(np.mean(agrees)))
        self.numerics.series_point("spec_accept_rate", step,
                                   self._live_acceptance())
        self.shadow_s += time.monotonic() - t0

    def _sample_one(self, req: Request, logits: jax.Array) -> int:
        req.state = RUNNING
        tok = self._sample(
            logits, jnp.asarray([req.sampling.temperature], jnp.float32),
            jnp.asarray([req.sampling.top_k], jnp.int32),
            jnp.asarray([req.sampling.seed], jnp.int32),
            jnp.asarray([len(req.output)], jnp.int32))
        return self._chosen(req, int(tok[0]), logits[0])

    @staticmethod
    def _chosen(req: Request, sampled: int, row: jax.Array) -> int:
        """The token ``req`` emits next — its forced token, if it has one
        — keeping the logits ``row`` it came from when asked."""
        if req.logits is not None:
            req.logits.append(row)
        if req.forced is not None:
            return int(req.forced[len(req.output)])
        return sampled

    def _emit(self, req: Request, tok: int, finished: list[Request]) -> None:
        req.output.append(tok)
        self.tokens_generated += 1
        now = time.monotonic()
        req.token_t.append(now)
        tr = self.obs.trace
        if not req.first_tok_t:
            req.first_tok_t = req.last_tok_t = now
            self._m_ttft.observe(req.ttft_s)
            if tr.enabled:
                tid = request_tid(req.rid)
                tr.instant("first_token", tid, token=tok,
                           ttft_s=req.ttft_s)
                tr.begin("decode", tid)
        else:
            self._m_itl.observe(now - req.last_tok_t)
            req.last_tok_t = now
        if self.eos_id is not None and tok == self.eos_id:
            reason = "eos"
        elif len(req.output) >= req.max_new_tokens:
            reason = "length"
        else:
            return
        self.sched.finish(req, reason, self.step_count)
        finished.append(req)
        self._m_req_finished[reason].inc()
        if tr.enabled:
            tid = request_tid(req.rid)
            tr.end("decode", tid)
            tr.end("request", tid, reason=reason, tokens=len(req.output))
