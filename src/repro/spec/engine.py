"""The speculative serving engine: draft k, verify k+1, accept j+1, roll
back the rest.

``SpecEngine`` replaces the plain engine's one-token decode with a
draft/verify round per scheduling step:

  1. **draft** — the proposer autoregressively proposes up to k tokens per
     running slot against its mirrored paged pool (per-slot effective k is
     capped at remaining-budget - 1 and at the slot's block reservation, so
     proposal writes can never escape the blocks admission reserved);
  2. **verify** — ONE jitted ``decoder.verify_step_paged`` scores all k+1
     positions per slot against the target pool (causal intra-chunk masks,
     per-slot position offsets, per-token activation scales);
  3. **accept** — ``sampling.speculative_verify_tokens`` applies the
     lossless accept/resample rule; greedy rows emit the target argmax
     chain token-for-token (the parity oracle vs the plain engine);
  4. **rollback** — slots advance by ACCEPTED length only: ``n_cached``
     grows by j+1, the proposal high-water mark is kept in ``n_written``,
     and rejected positions stay dead behind the length mask until the
     next round overwrites them.  ``Scheduler.rollback_to`` (pool
     ``truncate_to``) releases whole blocks the accepted length no longer
     justifies at early finish.

A slot whose remaining budget is 1 degenerates to a plain decode step
(k_eff == 0) through the same compiled verify function, so the engine
needs no second decode path.

That positional rollback story only exists for paged KV.  Slab-state plans
(recurrent RWKV6 / RG-LRU, encoder-conditioned Whisper) have *cumulative*
per-layer state — consuming a rejected token pollutes it irreversibly — so
their round switches to the protocol's ``snapshot`` / ``restore_select``:
verify runs as k+1 sequential single-token ``decode_step_slots`` calls
(each reusing THE plain engine's jitted decode, so every scored position
is bitwise the plain engine's — greedy parity by construction), snapshotting
the immutable state tree after each consumed token; after acceptance each
slot's state is restored to the snapshot matching its emitted length, and
the slab draft proposer restores its own snapshot chain to the confirmed
prefix.  Lossless across ALL state kinds.
"""
from __future__ import annotations

import dataclasses
import time
import types

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import decoder
from repro.serve import sampling
from repro.serve.engine import Engine
from repro.serve.scheduler import Request

from .proposer import DraftProposer, SlabDraftProposer, self_draft_model


class SpecEngine(Engine):
    """Speculative-decoding engine over the continuous-batching substrate.

    ``draft_k``: proposal length k (every verify scores k+1 positions).
    ``draft``: "self-qdq" (the target's own QDQ forward proposes — the
    acceptance ceiling for a QAD pair), "self-truncate" (first
    ``draft_layers`` layers of the same model, default half), or
    "two-model" (pass ``draft_model=(dcfg, dparams, dqcfg)`` — a small
    distilled student drafting for the packed target).  Greedy outputs are
    token-for-token identical to the plain ``Engine`` for EVERY draft mode;
    the draft only moves the acceptance rate.
    """

    scores_per_token = False

    def __init__(self, cfg, params, qcfg=None, *, draft_k: int = 4,
                 draft: str = "self-qdq", draft_layers: int = 0,
                 draft_model=None, adaptive_k: bool = False, **kw):
        super().__init__(cfg, params, qcfg, max_q_len=draft_k + 1, **kw)
        if draft_k < 1:
            raise ValueError(f"draft_k must be >= 1, got {draft_k}")
        self.spec_k = int(draft_k)
        self.draft_mode = draft if draft_model is None else "two-model"
        # verify numerics: per-position activation scales (+ per-token MoE
        # dispatch) make each of the k+1 scored positions bit-compatible
        # with a sequential one-token decode — see decoder.verify_step_paged
        self.vsq = dataclasses.replace(self.sq, act_scope="token")
        self.vcfg = (dataclasses.replace(self.cfg, moe_dispatch="token")
                     if self.cfg.n_experts else self.cfg)

        if draft_model is not None:
            dcfg, dparams, dqcfg = draft_model
        elif draft in ("self-qdq", "self-truncate"):
            # derive from self.params (TP: already sharded; slices keep
            # their NamedShardings)
            dcfg, dparams = self_draft_model(
                self.cfg, self.params, mode=draft.removeprefix("self-"),
                n_layers=draft_layers)
            dqcfg = self.sq
        else:
            raise ValueError(f"unknown draft mode {draft!r} "
                             "(pass draft_model= for two-model)")
        if dcfg.vocab_size != cfg.vocab_size:
            raise ValueError("draft and target vocabularies differ")
        if self.paged:
            self.proposer = DraftProposer(
                dcfg, dparams, dqcfg, pool=self.pool, mesh=self.mesh,
                rules=self.rules, fused=self.fused, obs=self.obs,
                prefill_scope=("token" if self.prefill_mode == "paged"
                               else "row"))
            self._verify = jax.jit(
                lambda params, pool, bt, lens, active, nprop, toks:
                self._traced(decoder.verify_step_paged, self.vcfg, params,
                             pool, bt, lens, active, nprop,
                             {"tokens": toks}, self.vsq,
                             fused=self.fused),
                donate_argnums=(1,))
        else:
            if dcfg.family != self.cfg.family:
                raise ValueError(
                    "slab-state speculative serving needs a draft of the "
                    f"target's family; got {dcfg.family!r} for "
                    f"{self.cfg.family!r}")
            self.proposer = SlabDraftProposer(dcfg, dparams, dqcfg,
                                              engine=self,
                                              s_alloc=self.s_alloc)
        self._accept = jax.jit(sampling.speculative_verify_tokens)

        self.verify_steps = 0
        self.verify_slot_rounds = 0      # one per (running slot, verify step)
        self.drafted_tokens = 0
        self.accepted_tokens = 0
        self.rolled_back_tokens = 0

        # --- speculative telemetry (repro.obs) -----------------------------
        # the draft kind is fixed at construction, so the per-draft-kind
        # counters are bound once; the accounting loop pays plain inc()s.
        # acceptance_rate doubles as the live QAD closeness signal: the
        # fraction of student proposals the NVFP4 target endorses.
        m = self.obs.metrics
        kind = {"draft": self.draft_mode}
        self._m_drafted = m.counter(
            "spec_draft_tokens_total", "draft tokens proposed",
            labels=("draft",)).labels(**kind)
        self._m_accepted = m.counter(
            "spec_accepted_tokens_total",
            "draft tokens the verify step accepted",
            labels=("draft",)).labels(**kind)
        self._m_rolled_back = m.counter(
            "spec_rolled_back_tokens_total",
            "draft tokens rejected and rolled back",
            labels=("draft",)).labels(**kind)
        self._m_draft_s = m.histogram(
            "spec_draft_seconds", "wall time of one round's draft phase")
        self._m_verify_s = m.histogram(
            "spec_verify_seconds",
            "wall time of one round's verify + accept phase")

        # --- draft-cost-aware adaptive k (ROADMAP next step) ---
        # choose per-slot draft length k* = argmax over 1..draft_k of
        # (expected emitted tokens) / (k·t_draft + t_verify), with the
        # acceptance probability taken from the slot's own measured history
        # (falling back to the engine EWMA until it has one) and the costs
        # from measured draft-step / verify-step wall clock.  Losslessness
        # never depends on k, so adapting it only moves throughput.
        self.adaptive_k = bool(adaptive_k)
        self.chosen_k: dict[int, int] = {}  # k -> times chosen (post-clamp)
        self._acc_ewma: float | None = None
        self._draft_tok_s: float | None = None   # EWMA draft s/token
        self._verify_s: float | None = None      # EWMA verify s/step
        self._req_acc: dict[int, tuple] = {}     # rid -> (drafted, accepted)

    # -- hooks -------------------------------------------------------------

    def _after_prefill(self, req: Request) -> None:
        self.proposer.prefill_request(req)

    def _live_acceptance(self):
        """Cumulative acceptance rate — the live cross-check series the
        numerics shadow probe plots against ``qad_live_kl`` (acceptance is
        the fraction of draft proposals the NVFP4 target endorses, i.e. a
        behavioural KL-closeness signal measured for free)."""
        if not self.drafted_tokens:
            return None
        return self.accepted_tokens / self.drafted_tokens

    # -- the draft/verify/accept round -------------------------------------

    def _do_decode(self, finished: list[Request]) -> None:
        if self.paged:
            self._do_decode_paged(finished)
        else:
            self._do_decode_stepped(finished)

    def _round_state(self, reqs):
        """Per-slot round arrays shared by both verify paths."""
        ns, k = self.n_slots, self.spec_k
        last = np.zeros((ns,), np.int32)
        prev = np.zeros((ns,), np.int32)
        lens = np.zeros((ns,), np.int32)
        active = np.zeros((ns,), bool)
        bt = np.zeros((ns, self.max_blocks_per_slot), np.int32)
        k_eff = np.zeros((ns,), np.int32)
        draft_lens = np.zeros((ns,), np.int32)
        temps = np.zeros((ns,), np.float32)
        topks = np.zeros((ns,), np.int32)
        seeds = np.zeros((ns,), np.int32)
        idxs = np.zeros((ns,), np.int32)
        for r in reqs:
            s = r.slot
            last[s] = r.output[-1]
            prev[s] = r.output[-2] if len(r.output) > 1 else r.prompt[-1]
            lens[s] = r.n_cached
            active[s] = True
            bt[s, : len(r.block_ids)] = r.block_ids
            draft_lens[s] = r.draft_cached
            remaining = r.max_new_tokens - len(r.output)
            cap = self.state.draft_cap(r)
            k_want = self._choose_k(r) if self.adaptive_k else k
            k_eff[s] = max(0, min(k_want, remaining - 1, cap))
            if self.adaptive_k:
                ke = int(k_eff[s])
                self.chosen_k[ke] = self.chosen_k.get(ke, 0) + 1
            temps[s] = r.sampling.temperature
            topks[s] = r.sampling.top_k
            seeds[s] = r.sampling.seed
            idxs[s] = len(r.output)
        return types.SimpleNamespace(
            bt=bt, lens=lens, active=active, k_eff=k_eff, last_tok=last,
            prev_tok=prev, draft_lens=draft_lens, temps=temps, topks=topks,
            seeds=seeds, tok_idx=idxs)

    def _account_round(self, reqs, out_toks, n_emit, n_acc, k_eff,
                       finished):
        """Advance requests by their ACCEPTED tokens; returns per-slot
        (emitted-count, confirmed-draft-advance) arrays for the slab path's
        snapshot restores."""
        sel = np.zeros((self.n_slots,), np.int32)
        adv = np.zeros((self.n_slots,), np.int32)
        for r in reqs:
            s = r.slot
            ne, j, ke = int(n_emit[s]), int(n_acc[s]), int(k_eff[s])
            self.drafted_tokens += ke
            self.accepted_tokens += j
            self.rolled_back_tokens += ke - j
            self._m_drafted.inc(ke)
            self._m_accepted.inc(j)
            self._m_rolled_back.inc(ke - j)
            if ke:
                d0, a0 = self._req_acc.get(r.rid, (0, 0))
                self._req_acc[r.rid] = (d0 + ke, a0 + j)
                rate = j / ke
                self._acc_ewma = (rate if self._acc_ewma is None
                                  else 0.7 * self._acc_ewma + 0.3 * rate)
            toks_emit = [int(out_toks[s, t]) for t in range(ne)]
            if self.eos_id is not None and self.eos_id in toks_emit:
                # EOS mid-pack: the accepted tail after EOS is discarded
                toks_emit = toks_emit[: toks_emit.index(self.eos_id) + 1]
            base = r.n_cached
            r.n_cached = base + len(toks_emit)        # accepted length only
            r.n_written = max(r.n_written, base + ke + 1)
            r.draft_cached = base + min(j + 1, ke)
            sel[s] = len(toks_emit)
            adv[s] = min(j + 1, ke)
            self.decode_tokens += len(toks_emit)
            self._m_tok_decode.inc(len(toks_emit))
            for tok in toks_emit:
                self._emit(r, tok, finished)
            if r.done:
                self._req_acc.pop(r.rid, None)   # bounded per-slot history
        return sel, adv

    def _do_decode_paged(self, finished: list[Request]) -> None:
        reqs = self.sched.running()
        if reqs:
            # on-demand paging: the verify write (position n_cached) must
            # fit — grow, evicting/preempting as needed; draft depth beyond
            # that is best-effort extra room that never preempts (draft_cap
            # then reads the grown table)
            reqs = self._ensure_decode_capacity(reqs, extra=self.spec_k)
        if not reqs:
            return
        tr = self.obs.trace
        t0 = time.monotonic()
        # the whole draft/verify round IS this engine's decode step — the
        # engine-lane span names are shared with the plain engine so one
        # trace schema covers both (spec.* spans nest inside it)
        with tr.annotate("engine.decode_step", n_active=len(reqs)):
            with tr.annotate("engine.decode.inputs"):
                st = self._round_state(reqs)
            with tr.annotate("spec.draft", n_active=len(reqs),
                             k=self.spec_k):
                draft_toks, draft_probs = self.proposer.propose(st,
                                                                self.spec_k)
            t_draft = time.monotonic() - t0

            tokens = np.concatenate([st.last_tok[:, None], draft_toks],
                                    axis=1)
            with tr.annotate("spec.verify", n_active=len(reqs)):
                logits, self.pool.data = self._compile_watch(
                    "verify", lambda: self._verify(
                        self.params, self.pool.data, jnp.asarray(st.bt),
                        jnp.asarray(st.lens), jnp.asarray(st.active),
                        jnp.asarray(st.k_eff), jnp.asarray(tokens)))
                accepted = self._accept(
                    logits, jnp.asarray(draft_toks),
                    jnp.asarray(draft_probs), jnp.asarray(st.k_eff),
                    jnp.asarray(st.temps), jnp.asarray(st.topks),
                    jnp.asarray(st.seeds), jnp.asarray(st.tok_idx))
                with tr.annotate("engine.decode.wait"):
                    out_toks, n_emit, n_acc = map(np.asarray, accepted)

            dt = time.monotonic() - t0
            self._observe_costs(t_draft, dt - t_draft,
                                int(st.k_eff.max(initial=0)))
            self._note_decode_step(dt, len(reqs))
            self._m_draft_s.observe(t_draft)
            self._m_verify_s.observe(dt - t_draft)
            self.verify_steps += 1
            self.verify_slot_rounds += len(reqs)
            with tr.annotate("engine.decode.emit", n_tokens=int(n_emit.sum())):
                self._account_round(reqs, out_toks, n_emit, n_acc, st.k_eff,
                                    finished)

    def _do_decode_stepped(self, finished: list[Request]) -> None:
        """Slab-state round: sequential stepped verify + snapshot/restore.

        Each of the k+1 scored positions is one masked call of THE plain
        engine's jitted ``decode_step_slots`` (row-scope numerics), so the
        i-th scored logits are bitwise what the plain engine would produce
        after the same accepted prefix + i round tokens — greedy outputs
        match the plain engine token for token for every draft mode.
        Snapshot S_i (a zero-copy reference; the slab step never donates)
        captures the state after consuming i round tokens; after acceptance
        each slot restores S[#emitted] and the proposer's mirrored chain
        restores its confirmed prefix.
        """
        reqs = self.sched.running()
        if not reqs:
            return
        tr = self.obs.trace
        t0 = time.monotonic()
        ns, k = self.n_slots, self.spec_k
        with tr.annotate("engine.decode_step", n_active=len(reqs)):
            with tr.annotate("engine.decode.inputs"):
                st = self._round_state(reqs)
            with tr.annotate("spec.draft", n_active=len(reqs), k=k):
                draft_toks, draft_probs = self.proposer.propose(st, k)
            t_draft = time.monotonic() - t0

            tokens = np.concatenate([st.last_tok[:, None], draft_toks],
                                    axis=1)
            logits = np.zeros((ns, k + 1, self.cfg.vocab_size), np.float32)
            snaps = [self.state.snapshot()]
            with tr.annotate("spec.verify", n_active=len(reqs)):
                for i in range(k + 1):
                    act_i = st.active & (i <= st.k_eff)
                    lg = self._compile_watch(
                        "decode", lambda: self.state.decode(
                            reqs, tokens[:, i:i + 1], st.lens + i, act_i))
                    logits[:, i] = np.asarray(lg[:, 0, :], np.float32)
                    snaps.append(self.state.snapshot())
                accepted = self._accept(
                    jnp.asarray(logits), jnp.asarray(draft_toks),
                    jnp.asarray(draft_probs), jnp.asarray(st.k_eff),
                    jnp.asarray(st.temps), jnp.asarray(st.topks),
                    jnp.asarray(st.seeds), jnp.asarray(st.tok_idx))
                with tr.annotate("engine.decode.wait"):
                    out_toks, n_emit, n_acc = map(np.asarray, accepted)

            dt = time.monotonic() - t0
            self._observe_costs(t_draft, dt - t_draft,
                                int(st.k_eff.max(initial=0)))
            self._note_decode_step(dt, len(reqs))
            self._m_draft_s.observe(t_draft)
            self._m_verify_s.observe(dt - t_draft)
            self.verify_steps += 1
            self.verify_slot_rounds += len(reqs)
            with tr.annotate("engine.decode.emit", n_tokens=int(n_emit.sum())):
                sel, adv = self._account_round(reqs, out_toks, n_emit, n_acc,
                                               st.k_eff, finished)
            # lossless rollback: every slot's state becomes exactly the
            # state after its emitted tokens — bitwise, never having drafted
            with tr.annotate("spec.rollback", n_active=len(reqs)):
                self.state.restore_select(snaps, sel)
                self.proposer.commit(adv)

    # -- draft-cost-aware adaptive k ---------------------------------------

    def _observe_costs(self, draft_s: float, verify_s: float,
                       n_draft_steps: int) -> None:
        """EWMA the measured per-token draft cost and per-step verify cost."""
        if n_draft_steps > 0:
            per_tok = draft_s / n_draft_steps
            self._draft_tok_s = (per_tok if self._draft_tok_s is None
                                 else 0.7 * self._draft_tok_s + 0.3 * per_tok)
        self._verify_s = (verify_s if self._verify_s is None
                          else 0.7 * self._verify_s + 0.3 * verify_s)

    def _acceptance_for(self, req: Request) -> float:
        """Per-token acceptance estimate for one slot: its own history once
        it has >= 4 drafted tokens, else the engine EWMA, else optimistic
        (start at full k and let the measurements pull it down)."""
        d, a = self._req_acc.get(req.rid, (0, 0))
        if d >= 4:
            return a / d
        if self._acc_ewma is not None:
            return self._acc_ewma
        return 1.0

    def _choose_k(self, req: Request) -> int:
        """k* = argmax_k E[emitted tokens | k] / (k·t_draft + t_verify).

        With per-token acceptance probability a, a length-k draft expects
        a·(1-a^k)/(1-a) accepted tokens plus the always-emitted bonus /
        correction token.  Until both costs are measured (the first round)
        the static ``spec_k`` is used.

        The model treats cost as per-slot, but a batch pays draft cost at
        max-over-slots k_eff (the proposer's sequential loop) and a fixed
        spec_k+1-wide verify: a single low-acceptance slot choosing a small
        k saves rolled-back KV writes immediately, and wall clock only once
        the other slots' acceptance (and hence their k*) drops too — the
        homogeneous case a distilled draft/target pair serves.
        """
        if self._draft_tok_s is None or self._verify_s is None:
            return self.spec_k
        a = min(max(self._acceptance_for(req), 0.0), 0.999)
        best_k, best_rate = 1, -1.0
        for k in range(1, self.spec_k + 1):
            e_acc = a * (1.0 - a ** k) / (1.0 - a)
            rate = (e_acc + 1.0) / (k * self._draft_tok_s + self._verify_s)
            if rate > best_rate:
                best_rate, best_k = rate, k
        return best_k

    # -- reporting ---------------------------------------------------------

    def stats(self) -> dict:
        d = super().stats()
        d.update({
            "speculative": True,
            "spec_k": self.spec_k, "draft_mode": self.draft_mode,
            "verify_steps": self.verify_steps,
            "verify_slot_rounds": self.verify_slot_rounds,
            "drafted_tokens": self.drafted_tokens,
            "accepted_tokens": self.accepted_tokens,
            "rolled_back_tokens": self.rolled_back_tokens,
            # None (not 0.0) before any draft/verify round has run — "no
            # data" and "nothing accepted" are different answers
            "acceptance_rate": (self.accepted_tokens / self.drafted_tokens
                                if self.drafted_tokens else None),
            # tokens a slot emits per verify round (accepted + the always-
            # emitted correction/bonus token): 1.0 = no speculation win,
            # k+1 = every proposal accepted
            "accepted_per_step": ((self.accepted_tokens
                                   + self.verify_slot_rounds)
                                  / self.verify_slot_rounds
                                  if self.verify_slot_rounds else None),
            "draft_pool_bytes": self.proposer.nbytes(),
            "adaptive_k": self.adaptive_k,
            # chosen-k distribution (post-clamp; populated when adaptive)
            "chosen_k_hist": dict(sorted(self.chosen_k.items())),
        })
        return d
