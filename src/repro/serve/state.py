"""Per-layer serve-state protocol: one engine, many cache architectures.

The continuous-batching engine used to hard-code "request state == paged KV
blocks".  This module generalizes that into a protocol with two backends,
selected from the config's per-layer state plan
(``models.registry.serve_state_plan``):

  * ``PagedKVState``  — plan ("paged_kv",): the block-granular KV pool,
    exactly the pre-refactor semantics (block-table decode, capacity-based
    admission in blocks, rollback by page truncation).
  * ``SlabState``     — any other supported plan: per-slot constant-size
    state slabs (RWKV6 / RG-LRU recurrent state, RG-LRU window-KV rings,
    encoder-decoder dense self-KV + immutable encoder-output slots).  The
    slot index IS the state address; decode is the model's batched
    ``decode_step_slots`` at per-slot positions.

Both answer the same contract the engine and scheduler program against:

    admission_check / can_reserve / reserve / release      (alloc + free)
    write_prefill                                          (prefill_write)
    block_table / decode                                   (decode_step)
    snapshot / restore_select / rollback_to / draft_cap    (speculative)
    stats / leaked                                         (telemetry)

Speculative rollback differs fundamentally between the two: paged KV is
position-addressed, so rejected draft positions are simply overwritten
(page truncation only releases whole dead blocks at finish); recurrent
state is *cumulative* — a rejected draft token pollutes the state
irreversibly — so the slab backend snapshots the whole (immutable) state
tree per verify position and restores the per-slot tree matching each
slot's accepted length.  Snapshots are zero-copy references, which is why
the slab decode step never donates its state buffers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import device_bytes
from repro.models import common, decoder
from repro.models.registry import get_model, serve_capabilities

from .paged_kv import PagedKVPool, PoolExhausted, PrefixCache


class UnsupportedStateError(ValueError):
    """A config's state plan needs a kind this engine doesn't implement."""


def check_supported(cfg) -> tuple:
    """Return the config's state plan or raise a one-line capability error."""
    caps = serve_capabilities(cfg)
    if not caps["supported"]:
        raise UnsupportedStateError(
            f"{cfg.name}: engine cannot serve state kind(s) "
            f"{', '.join(caps['missing'])} "
            f"(plan: {' + '.join(caps['plan'])})")
    return caps["plan"]


def make_state(engine, cfg, *, n_slots, block_size, n_blocks,
               max_blocks_per_slot, s_alloc, kv_alloc="reserve",
               headroom=2, prefix_cache=False):
    """Build the state backend for ``cfg``'s plan (or raise a capability
    error).  ``engine`` supplies params/sq and the TP plumbing
    (``_traced`` / ``_shard``); the backend owns the device state and the
    jitted step functions that touch it."""
    plan = check_supported(cfg)
    if plan == ("paged_kv",):
        return PagedKVState(engine, cfg, n_blocks=n_blocks,
                            block_size=block_size,
                            max_blocks_per_slot=max_blocks_per_slot,
                            kv_alloc=kv_alloc, headroom=headroom,
                            prefix_cache=prefix_cache)
    if kv_alloc != "reserve" or prefix_cache:
        raise UnsupportedStateError(
            f"{cfg.name}: on-demand paging / prefix caching needs the "
            f"paged_kv state plan (plan: {' + '.join(plan)})")
    return SlabState(engine, cfg, n_slots=n_slots, s_alloc=s_alloc, plan=plan)


# ---------------------------------------------------------------------------
# shared slab machinery (also used by the speculative slab draft proposer)
# ---------------------------------------------------------------------------


def slab_write(specs, data, cache, slot):
    """Scatter a batch=1 prefill cache into one slot of every slab leaf.

    Each cache leaf is right-padded with zeros up to the slab's size on
    every non-batch axis (the dense self-KV case: a length-P prompt into an
    S_alloc slab — the same zero padding ``prefill(s_max=...)`` would
    apply), then written at ``slot`` along the spec's "batch" axis.
    Traced: jit per prompt length.
    """
    def one(spec, d, c):
        ax = spec.axes.index("batch")
        pads = [(0, 0) if i == ax else (0, ds - cs)
                for i, (ds, cs) in enumerate(zip(d.shape, c.shape))]
        if any(hi for _, hi in pads):
            c = jnp.pad(c, pads)
        starts = [0] * d.ndim
        starts[ax] = slot
        return jax.lax.dynamic_update_slice(d, c.astype(d.dtype),
                                            tuple(starts))
    return jax.tree.map(one, specs, data, cache, is_leaf=common.is_spec)


def slab_restore_select(specs, snaps, sel):
    """Per-slot state restore from a snapshot chain.

    ``snaps``: list of K full state trees (immutable snapshots);
    ``sel`` [n_slots] picks, per slot, which snapshot's per-slot tree to
    keep.  Exact gather — no arithmetic — so the restored slot is bit for
    bit the state it had when its chosen snapshot was taken.  Traced: jit
    per chain length.
    """
    def one(spec, *leaves):
        ax = spec.axes.index("batch")
        st = jnp.stack(leaves)                       # [K, ...leaf shape]
        m = jnp.moveaxis(st, ax + 1, 1)              # [K, n_slots, rest...]
        out = m[sel, jnp.arange(sel.shape[0])]       # [n_slots, rest...]
        return jnp.moveaxis(out, 0, ax)              # batch axis back home
    return jax.tree.map(one, specs, *snaps, is_leaf=common.is_spec)


def slab_bytes_per_slot(specs, n_slots: int) -> int:
    """Constant per-request state footprint of a slab spec tree."""
    return common.spec_bytes(specs) // max(n_slots, 1)


def _tree_nbytes(tree) -> int:
    return sum(int(a.nbytes) for a in jax.tree.leaves(tree))


# ---------------------------------------------------------------------------
# paged backend
# ---------------------------------------------------------------------------


class PagedKVState:
    """Protocol adapter over the block-granular ``PagedKVPool``.

    Admission reasons in blocks (worst-case reservation up front — decode
    never exhausts the pool mid-flight), decode runs
    ``decoder.decode_step_paged`` with per-slot block tables and donates
    the pool buffers, and speculative rollback is positional: rejected
    draft KV stays dead behind the length mask until overwritten, with
    ``truncate_to`` releasing whole dead blocks at finish.
    """

    def __init__(self, engine, cfg, *, n_blocks, block_size,
                 max_blocks_per_slot, kv_alloc="reserve", headroom=2,
                 prefix_cache=False):
        self.eng = engine
        self.cfg = cfg
        self.kinds = ("paged_kv",)
        self.required_extras: tuple = ()
        self.max_blocks_per_slot = max_blocks_per_slot
        if kv_alloc not in ("reserve", "ondemand"):
            raise ValueError(f"unknown kv_alloc mode {kv_alloc!r}")
        self.kv_alloc = kv_alloc
        self.headroom = int(headroom)
        self.pool = PagedKVPool(
            engine._shard(decoder.init_paged_pool(cfg, n_blocks, block_size),
                          decoder.paged_pool_specs(cfg, n_blocks, block_size)),
            block_size)
        self.cache = (PrefixCache(self.pool,
                                  f"{cfg.name}|{engine.sq!r}")
                      if prefix_cache else None)
        self._decode_fn = jax.jit(
            lambda params, pool, bt, lens, active, toks:
            engine._traced(decoder.decode_step_paged, cfg, params, pool,
                           bt, lens, active, {"tokens": toks}, engine.sq,
                           fused=engine.fused),
            donate_argnums=(1,))
        self._write_fns: dict[int, object] = {}
        self._copy_fn = None

    # -- capacity ----------------------------------------------------------

    def admission_check(self, req) -> None:
        need = self.pool.blocks_for(req.max_cached)
        if need > self.max_blocks_per_slot or need > self.pool.n_blocks:
            raise ValueError(
                f"request needs {need} blocks > "
                f"max_blocks_per_slot={self.max_blocks_per_slot} or "
                f"pool capacity={self.pool.n_blocks} "
                f"(prompt {req.prompt_len} + gen {req.max_new_tokens}); "
                "it could never be admitted")

    def _free_plus_evictable(self) -> int:
        ev = self.cache.evictable if self.cache is not None else 0
        return self.pool.free_blocks + ev

    def _hit_blocks(self, ctx) -> int:
        return self.cache.lookup(ctx) if self.cache is not None else 0

    def _admit_capacity(self, ctx) -> tuple[int, int]:
        """(cache hits for ``ctx``, blocks deliverable AFTER taking them).

        Acquiring a hit revives a CACHED block: it stops being evictable
        but consumes no free block.  Counting every hit as if it were
        cached keeps this estimate <= what ``reserve`` can actually
        deliver (an over-count here would admit a request that reserve()
        then cannot satisfy)."""
        hits = self._hit_blocks(ctx)
        ev = self.cache.evictable if self.cache is not None else 0
        return hits, self.pool.free_blocks + max(ev - hits, 0)

    def can_reserve(self, req) -> bool:
        if self.kv_alloc == "reserve":
            need = self.pool.blocks_for(req.max_cached)
            if self.cache is None:
                return self.pool.can_alloc(need)
            hits, avail = self._admit_capacity(req.resume_tokens())
            return avail >= need - hits
        # on-demand: admit on the blocks the prefill needs NOW plus a small
        # headroom watermark so the first decode growths don't instantly
        # preempt; the watermark is waived when nothing is running (an empty
        # pool must always admit — admission_check bounded the worst case)
        ctx = req.resume_tokens()
        hits, avail = self._admit_capacity(ctx)
        need = self.pool.blocks_for(len(ctx)) - hits
        slack = self.headroom if self.pool.active_blocks > 0 else 0
        return avail >= need + slack

    def _ensure_free(self, n: int) -> bool:
        """Evict LRU unreferenced cache entries until ``n`` blocks are on
        the free list.  Returns False if the pool can't get there."""
        short = n - self.pool.free_blocks
        if short > 0 and self.cache is not None:
            self.eng._count_cache_evict(len(self.cache.evict(short)))
            short = n - self.pool.free_blocks
        return short <= 0

    def reserve(self, req) -> None:
        hits: list[int] = []
        if self.cache is not None:
            hits = self.cache.acquire(req.resume_tokens())
            req.n_cache_hit = len(hits) * self.pool.block_size
        if self.kv_alloc == "reserve":
            need = self.pool.blocks_for(req.max_cached) - len(hits)
        else:
            need = self.pool.blocks_for(len(req.resume_tokens())) - len(hits)
        need = max(need, 0 if hits else 1)
        if not self._ensure_free(need):
            # can_reserve said yes, so this only races with same-step churn
            self.pool.free(hits)
            req.n_cache_hit = 0
            raise PoolExhausted(
                f"need {need} blocks, {self.pool.free_blocks} free")
        req.block_ids = hits + self.pool.alloc(need)

    def grow_to(self, req, n_tokens: int) -> bool:
        """On-demand growth: extend the request's block table to cover
        ``n_tokens`` cached positions, evicting unreferenced cache entries
        as needed.  Returns False when the pool is exhausted (the engine
        then preempts a running request and retries)."""
        target = min(self.pool.blocks_for(n_tokens), self.max_blocks_per_slot)
        while len(req.block_ids) < target:
            if not self._ensure_free(1):
                return False
            req.block_ids += self.pool.alloc(1)
        return True

    def register_prefix(self, req, ctx) -> int:
        """Register the full-block prefix of a freshly prefilled context so
        later requests (and this one after preemption) can share it."""
        if self.cache is None:
            return 0
        return self.cache.register(ctx, req.block_ids)

    def make_writable(self, req, i: int) -> int:
        """Copy-on-write guard for block ``i`` of the request's table.

        Writing a block that other tables reference would corrupt their
        KV, and writing a registered block would diverge it from its
        hash.  Shared blocks get a fresh copy (device page duplicated,
        old reference dropped); privately held registered blocks are just
        deregistered.  The paged-prefill write pattern never hits the
        shared case (writes only target positions past the acquired
        prefix), so this is a defensive primitive, unit-tested directly.
        """
        b = req.block_ids[i]
        if self.pool.refcount(b) > 1:
            if not self._ensure_free(1):
                raise PoolExhausted("no free block for copy-on-write split")
            [nb] = self.pool.alloc(1)
            if self._copy_fn is None:
                # every layer's page of block src -> dst, whatever the
                # planes' minor dims ([.., Hkv*hd] pages, [.., Hkv] scales)
                self._copy_fn = jax.jit(
                    lambda data, src, dst: {
                        k: v.at[:, dst].set(v[:, src])
                        for k, v in data.items()},
                    donate_argnums=(0,))
            self.pool.data = self._copy_fn(
                self.pool.data, jnp.asarray(b, jnp.int32),
                jnp.asarray(nb, jnp.int32))
            self.pool.free([b])
            req.block_ids[i] = nb
            return nb
        if self.cache is not None:
            self.cache.drop_block(b)
        return b

    def rollback_to(self, req, n_tokens: int) -> int:
        req.block_ids, freed = self.pool.truncate_to(req.block_ids, n_tokens)
        req.n_written = min(req.n_written, n_tokens)
        return len(freed)

    def release(self, req) -> None:
        if req.block_ids:
            # two-stage release: the speculative tail first, then the live
            # prefix — both land on the free list the same step
            self.rollback_to(req, req.n_cached)
            self.pool.free(req.block_ids)
            req.block_ids = []

    # -- device state ------------------------------------------------------

    def write_prefill(self, req, cache) -> None:
        p = req.prompt_len
        if p not in self._write_fns:
            self._write_fns[p] = jax.jit(decoder.write_prompt_to_pool,
                                         donate_argnums=(0,))
        ids = np.asarray(req.block_ids[: self.pool.blocks_for(p)], np.int32)
        self.pool.data = self._write_fns[p](self.pool.data, cache,
                                            jnp.asarray(ids))

    def block_table(self, reqs):
        """The decode step's host input beside the per-slot arrays: each
        slot's block ids, [n_slots, max_blocks_per_slot]."""
        bt = np.zeros((self.eng.n_slots, self.max_blocks_per_slot), np.int32)
        for r in reqs:
            bt[r.slot, : len(r.block_ids)] = r.block_ids
        return bt

    def decode(self, reqs, toks, lens, active, bt):
        del reqs                               # ``bt`` holds their blocks
        logits, self.pool.data = self._decode_fn(
            self.eng.params, self.pool.data, jnp.asarray(bt),
            jnp.asarray(lens), jnp.asarray(active), jnp.asarray(toks))
        return logits

    # -- speculative -------------------------------------------------------

    def draft_cap(self, req) -> int:
        """Proposals may touch positions up to the block reservation - 1."""
        return len(req.block_ids) * self.pool.block_size - req.n_cached - 1

    # snapshot/restore is never needed here: rejected positions are dead by
    # the length mask and the next round's writes overwrite them in place

    # -- telemetry ---------------------------------------------------------

    def leaked(self) -> bool:
        """Refcount-aware leak check: blocks still referenced by a block
        table after drain are leaks; cached-but-unreferenced blocks are
        the prefix cache working as intended, not leaks."""
        if self.pool.active_blocks != 0:
            return True
        # drain-time consistency: everything off the free list must be
        # accounted for by the cache's retained set
        assert self.pool.used_blocks == self.pool.cached_blocks, (
            "pool blocks neither referenced, cached, nor free",
            self.pool.used_blocks, self.pool.cached_blocks)
        return False

    def occupancy(self) -> tuple[int, int]:
        """(used, capacity) in the backend's own allocation unit (blocks)."""
        return self.pool.occupancy()

    def nbytes(self) -> int:
        return self.pool.nbytes()

    def stats(self) -> dict:
        out = dict(self.pool.stats(), state_backend="paged_kv",
                   state_kinds=list(self.kinds), kv_alloc=self.kv_alloc)
        if self.cache is not None:
            out["prefix_cache"] = self.cache.stats()
        return out


# ---------------------------------------------------------------------------
# slab backend
# ---------------------------------------------------------------------------


class SlabState:
    """Per-slot constant-size state slabs for non-paged state plans.

    The model declares its per-slot state via ``slot_state_specs`` (batch
    dim == n_slots) and steps it via ``decode_step_slots`` (per-slot
    positions + active mask; inactive slots keep their state bit for bit).
    Capacity is trivial: one slab slot per engine slot, so admission never
    sees phantom block pressure — only plans with a finite dense component
    ("dense_kv": encoder-decoder self-attention) bound prompt + generation
    by the slab's sequence allocation.

    ``snapshot`` is a zero-copy reference to the (immutable) state tree —
    the decode jit deliberately does NOT donate its state argument — and
    ``restore_select`` gathers each slot's tree from a snapshot chain, the
    speculative engine's lossless rollback for cumulative recurrent state.
    """

    def __init__(self, engine, cfg, *, n_slots, s_alloc, plan):
        self.eng = engine
        self.cfg = cfg
        self.kinds = tuple(plan)
        self.model = get_model(cfg)
        self.n_slots = n_slots
        self.specs = self.model.slot_state_specs(cfg, n_slots, s_alloc)
        self.data = engine._shard(common.zeros_from_specs(self.specs),
                                  self.specs)
        # finite dense self-KV bounds admission; recurrent slabs and ring
        # windows are O(1) per slot regardless of sequence length
        self.dense_bound = s_alloc if "dense_kv" in self.kinds else None
        self.required_extras = ("enc_frames",) \
            if "encoder_output" in self.kinds else ()
        self.in_use = [False] * n_slots
        self.peak_used = 0
        self._decode_fn = jax.jit(
            lambda params, data, toks, lens, active:
            engine._traced(self.model.decode_step_slots, cfg, params, data,
                           {"tokens": toks}, lens, active, engine.sq))
        self._write_fns: dict[int, object] = {}
        self._restore_fns: dict[int, object] = {}

    # -- capacity ----------------------------------------------------------

    def admission_check(self, req) -> None:
        for k in self.required_extras:
            if not req.extras or k not in req.extras:
                raise ValueError(
                    f"{self.cfg.name}: request needs extras[{k!r}] "
                    "(encoder-conditioned arch)")
        if self.dense_bound is not None and req.max_cached > self.dense_bound:
            raise ValueError(
                f"request needs {req.max_cached} cached positions > "
                f"state slab capacity={self.dense_bound} "
                f"(prompt {req.prompt_len} + gen {req.max_new_tokens}); "
                "it could never be admitted")

    def can_reserve(self, req) -> bool:
        return True          # one slab slot per engine slot, nothing else

    def reserve(self, req) -> None:
        self.in_use[req.slot] = True
        self.peak_used = max(self.peak_used, sum(self.in_use))

    def rollback_to(self, req, n_tokens: int) -> int:
        # no positional storage to truncate — device-state rollback is the
        # speculative engine's snapshot/restore; only clamp the host mark
        req.n_written = min(req.n_written, n_tokens)
        return 0

    def release(self, req) -> None:
        if req.slot is not None:
            self.in_use[req.slot] = False

    # -- device state ------------------------------------------------------

    def write_prefill(self, req, cache) -> None:
        p = req.prompt_len
        if p not in self._write_fns:
            self._write_fns[p] = jax.jit(
                lambda data, cache, slot:
                slab_write(self.specs, data, cache, slot))
        self.data = self._write_fns[p](self.data, cache,
                                       jnp.asarray(req.slot, jnp.int32))

    def block_table(self, reqs):
        """Slabs need no host input beyond the per-slot arrays."""
        return None

    def decode(self, reqs, toks, lens, active, bt=None):
        del reqs, bt                           # slot index == state address
        logits, self.data = self._decode_fn(
            self.eng.params, self.data, jnp.asarray(toks),
            jnp.asarray(lens), jnp.asarray(active))
        return logits

    # -- speculative -------------------------------------------------------

    def draft_cap(self, req) -> int:
        if self.dense_bound is not None:
            return self.dense_bound - req.n_cached - 1
        return 1 << 30       # recurrent / ring state: no positional bound

    def snapshot(self):
        """Zero-copy: the state tree is immutable (no donation anywhere on
        the slab path), so holding the reference IS the snapshot."""
        return self.data

    def restore(self, snap) -> None:
        self.data = snap

    def restore_select(self, snaps, sel) -> None:
        """Set each slot's state to its tree in ``snaps[sel[slot]]``."""
        key = len(snaps)
        if key not in self._restore_fns:
            self._restore_fns[key] = jax.jit(
                lambda snaps, sel:
                slab_restore_select(self.specs, snaps, sel))
        self.data = self._restore_fns[key](list(snaps), jnp.asarray(sel))

    # -- telemetry ---------------------------------------------------------

    def leaked(self) -> bool:
        return any(self.in_use)

    def occupancy(self) -> tuple[int, int]:
        """(used, capacity) in the backend's own allocation unit (slots)."""
        return sum(self.in_use), self.n_slots

    def nbytes(self) -> int:
        return _tree_nbytes(self.data)

    def stats(self) -> dict:
        used = sum(self.in_use)
        return {
            "state_backend": "slab",
            "state_kinds": list(self.kinds),
            "n_slots": self.n_slots,
            "used_slots": used,
            "peak_used_slots": self.peak_used,
            "utilization": used / max(self.n_slots, 1),
            "peak_utilization": self.peak_used / max(self.n_slots, 1),
            "fp8": False,
            "pool_bytes": _tree_nbytes(self.data),
            "pool_bytes_per_device": device_bytes(self.data),
            "state_bytes_per_slot": slab_bytes_per_slot(self.specs,
                                                        self.n_slots),
            "state_dense_bound": self.dense_bound,
        }
