"""Attention: GQA, blockwise (flash-style) softmax, sliding window, KV cache.

Prefill at 32k would materialize S² score matrices; ``blockwise_attention``
scans over KV chunks with online-softmax statistics (the pure-JAX analogue of
flash attention — memory O(S·chunk), FLOPs unchanged), and chunks Q so the
working set stays VMEM-sized on TPU.

Decode attends one query against the cache.  The cache is either BF16 or FP8
(E4M3 values + per-(token, head) fp32 scales — the paper's Nemotron-3-Nano
recipe); sliding-window layers keep a ring buffer of the last ``window``
positions (RoPE is applied *before* caching, so slot order is irrelevant
given the validity mask).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import nvfp4
from repro.distributed.ctx import cst

NEG_INF = -1e30


def split_heads(x, n_heads, head_dim):
    b, s, _ = x.shape
    return x.reshape(b, s, n_heads, head_dim)


def repeat_kv(k, n_rep: int):
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)
                            ).reshape(b, s, h * n_rep, d)


def blockwise_attention(q, k, v, *, causal: bool, window: int = 0,
                        q_chunk: int = 1024, kv_chunk: int = 1024,
                        q_offset=0, kv_valid=None) -> jax.Array:
    """q: [B,Sq,H,hd], k/v: [B,Sk,Hkv,hd] -> [B,Sq,H,hd].

    ``q_offset``: absolute position of q[0] (for prefill-continuation).
    It may be a traced scalar (the engine's chunked prefill jits one step
    function for every chunk offset).
    ``window`` > 0 masks keys older than ``window`` positions (local attn).
    ``kv_valid``: optional (traced) count of valid key positions — keys at
    ``k_pos >= kv_valid`` are masked.  Defaults to the static key length,
    so callers may right-pad k/v to a fixed allocation and mask the tail;
    fully-masked kv chunks are exact no-ops in the online softmax (their
    probabilities underflow to 0.0 and the max statistic is unchanged).
    """
    b, sq0, h, hd = q.shape
    sk0, hkv = k.shape[1], k.shape[2]
    n_rep = h // hkv
    k, v = repeat_kv(k, n_rep), repeat_kv(v, n_rep)

    q_chunk = min(q_chunk, sq0)
    kv_chunk = min(kv_chunk, sk0)
    # pad seq dims up to chunk multiples (pad keys are masked via k_pos >= sk0)
    pq, pk = (-sq0) % q_chunk, (-sk0) % kv_chunk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    sq, sk = sq0 + pq, sk0 + pk
    nq, nk = sq // q_chunk, sk // kv_chunk
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))

    # [B,H,nq,cq,hd] / [B,H,nk,ck,hd]
    qc = q.transpose(0, 2, 1, 3).reshape(b, h, nq, q_chunk, hd)
    kc = k.transpose(0, 2, 1, 3).reshape(b, h, nk, kv_chunk, hd)
    vc = v.transpose(0, 2, 1, 3).reshape(b, h, nk, kv_chunk, hd)

    q_pos = (jnp.arange(sq) + q_offset).reshape(nq, q_chunk)
    k_pos = jnp.arange(sk).reshape(nk, kv_chunk)

    def per_q_chunk(qi, qpos):
        # online softmax over kv chunks
        def body(carry, inp):
            m, l, acc = carry
            ki, vi, kpos = inp
            # bf16 MXU operands, fp32 accumulation (§Perf iteration G2:
            # halves score/probability HBM traffic vs fp32 operands)
            s = jnp.einsum("bhqd,bhkd->bhqk", qi, ki,
                           preferred_element_type=jnp.float32) * scale
            mask = jnp.broadcast_to(
                kpos[None, :] < (sk0 if kv_valid is None else kv_valid),
                (q_chunk, kv_chunk))
            if causal:
                mask = mask & (qpos[:, None] >= kpos[None, :])
            if window:
                mask = mask & (qpos[:, None] - kpos[None, :] < window)
            s = jnp.where(mask, s, NEG_INF)
            m2 = jnp.maximum(m, jnp.max(s, -1))
            p = jnp.exp(s - m2[..., None])
            corr = jnp.exp(m - m2)
            l2 = l * corr + jnp.sum(p, -1)
            acc2 = acc * corr[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p.astype(qi.dtype), vi,
                preferred_element_type=jnp.float32)
            return (m2, l2, acc2), None

        init = (jnp.full((b, h, q_chunk), NEG_INF, jnp.float32),
                jnp.zeros((b, h, q_chunk), jnp.float32),
                jnp.zeros((b, h, q_chunk, hd), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(
            body, init, (kc.transpose(2, 0, 1, 3, 4),
                         vc.transpose(2, 0, 1, 3, 4), k_pos))
        return acc / jnp.maximum(l, 1e-30)[..., None]

    out = jax.lax.map(lambda args: per_q_chunk(*args),
                      (qc.transpose(2, 0, 1, 3, 4), q_pos))   # [nq,B,H,cq,hd]
    out = out.transpose(1, 0, 3, 2, 4).reshape(b, sq, h, hd)
    return out[:, :sq0].astype(q.dtype)


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------


class KVCache(NamedTuple):
    """Per-layer-stacked cache.  fp8: k/v are E4M3 + per-(pos,head) scales."""
    k: jax.Array            # [L, B, S_max, Hkv, hd]
    v: jax.Array
    k_scale: jax.Array | None   # [L, B, S_max, Hkv] f32 (fp8 only)
    v_scale: jax.Array | None


def init_kv_cache(n_layers, batch, s_max, n_kv, head_dim, dtype_str="bf16"):
    shape = (n_layers, batch, s_max, n_kv, head_dim)
    if dtype_str == "fp8":
        return KVCache(
            k=jnp.zeros(shape, jnp.float8_e4m3fn),
            v=jnp.zeros(shape, jnp.float8_e4m3fn),
            k_scale=jnp.zeros(shape[:-1], jnp.float32),
            v_scale=jnp.zeros(shape[:-1], jnp.float32))
    return KVCache(k=jnp.zeros(shape, jnp.bfloat16),
                   v=jnp.zeros(shape, jnp.bfloat16), k_scale=None, v_scale=None)


def _quant_kv(x):
    """[B,S,H,hd] -> (e4m3 values, [B,S,H] scales) via the core FP8 algebra."""
    t = nvfp4.fp8_quantize(x, axis=-1)
    return t.values, t.scale[..., 0]


def _dequant_kv(vals, scale, dtype=jnp.bfloat16):
    return nvfp4.fp8_dequantize(nvfp4.FP8Tensor(vals, scale[..., None]), dtype)


def cache_update_layer(layer_cache, k_new, v_new, pos):
    """Write new kv at position(s) ``pos`` (scalar start index) into one
    layer's slice {k, v, k_scale, v_scale} (leading L removed)."""
    out = dict(layer_cache)
    if layer_cache.get("k_scale") is not None:
        kq, ks = _quant_kv(k_new)
        vq, vs = _quant_kv(v_new)
        out["k"] = jax.lax.dynamic_update_slice_in_dim(layer_cache["k"], kq, pos, 1)
        out["v"] = jax.lax.dynamic_update_slice_in_dim(layer_cache["v"], vq, pos, 1)
        out["k_scale"] = jax.lax.dynamic_update_slice_in_dim(
            layer_cache["k_scale"], ks, pos, 1)
        out["v_scale"] = jax.lax.dynamic_update_slice_in_dim(
            layer_cache["v_scale"], vs, pos, 1)
    else:
        out["k"] = jax.lax.dynamic_update_slice_in_dim(
            layer_cache["k"], k_new.astype(layer_cache["k"].dtype), pos, 1)
        out["v"] = jax.lax.dynamic_update_slice_in_dim(
            layer_cache["v"], v_new.astype(layer_cache["v"].dtype), pos, 1)
    return out


def cache_read_layer(layer_cache, dtype=jnp.bfloat16):
    if layer_cache.get("k_scale") is not None:
        return (_dequant_kv(layer_cache["k"], layer_cache["k_scale"], dtype),
                _dequant_kv(layer_cache["v"], layer_cache["v_scale"], dtype))
    return layer_cache["k"].astype(dtype), layer_cache["v"].astype(dtype)


def decode_attend(q, layer_cache, pos, *, window: int = 0):
    """One-token decode: q [B,1,H,hd] vs cache [B,S_max,Hkv,hd].

    ``pos``: number of valid cache positions (the new token's kv must already
    be written) — a scalar applied to every row, or a [B] array giving each
    row its own count (the slot-state engine batches requests at different
    sequence positions).  Sliding-window caches are ring buffers: validity is
    pos - window <= slot_pos < pos, where slot semantics are handled by the
    caller writing at ``pos % S_max``; since RoPE precedes caching, only the
    mask matters.  The mask arithmetic is pure boolean/integer work, so the
    per-row form is bitwise identical to the scalar form row by row.
    """
    k, v = cache_read_layer(layer_cache, q.dtype)
    b, s_max, hkv, hd = k.shape
    h = q.shape[2]
    k = repeat_kv(k, h // hkv)
    v = repeat_kv(v, h // hkv)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    slot = jnp.arange(s_max)[None, :]                  # [1, S_max]
    pos = jnp.asarray(pos)
    rpos = pos[:, None] if pos.ndim else pos[None, None]   # [B|1, 1]
    if window:
        # ring buffer: slot i currently holds absolute position
        #   p(i) = i + s_max * floor((pos-1-i)/s_max)  — the most recent write
        newest = rpos - 1
        abs_pos = slot + s_max * ((newest - slot) // s_max)
        valid = (abs_pos >= 0) & (abs_pos >= rpos - window) & (abs_pos <= newest)
    else:
        valid = slot < rpos
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, -1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def cache_update_slots(layer_cache, k_new, v_new, positions, active):
    """Per-row decode write into a dense [B, S_max, ...] cache layer.

    k_new/v_new: [B, 1, Hkv, hd]; positions: [B] per-row write slots (ring
    callers pass ``pos % S_max``); active: [B] bool — inactive rows scatter
    out of bounds and are dropped, leaving their cached values untouched.
    Quantization goes through the same ``_quant_kv`` as ``cache_update_layer``
    so a slot-batched write stores the scalar path's bits exactly.
    """
    b, s_max = layer_cache["k"].shape[:2]
    row = jnp.arange(b)
    pos_w = jnp.where(active, positions, s_max)        # OOB -> dropped
    out = dict(layer_cache)
    if layer_cache.get("k_scale") is not None:
        kq, ks = _quant_kv(k_new)
        vq, vs = _quant_kv(v_new)
        out["k"] = layer_cache["k"].at[row, pos_w].set(kq[:, 0], mode="drop")
        out["v"] = layer_cache["v"].at[row, pos_w].set(vq[:, 0], mode="drop")
        out["k_scale"] = layer_cache["k_scale"].at[row, pos_w].set(
            ks[:, 0], mode="drop")
        out["v_scale"] = layer_cache["v_scale"].at[row, pos_w].set(
            vs[:, 0], mode="drop")
    else:
        dt = layer_cache["k"].dtype
        out["k"] = layer_cache["k"].at[row, pos_w].set(
            k_new[:, 0].astype(dt), mode="drop")
        out["v"] = layer_cache["v"].at[row, pos_w].set(
            v_new[:, 0].astype(dt), mode="drop")
    return out


# ---------------------------------------------------------------------------
# Paged KV pool (continuous-batching engine)
#
# The pool stores one layer's cache as [n_blocks, block_size, Hkv, hd]
# (+ per-(slot-in-block, head) fp32 scales when FP8).  Requests own disjoint
# block sets; a per-request block table maps logical position p to pool
# location (table[p // block_size], p % block_size).  Unlike the dense
# ring-buffer cache above there is no wraparound: the slot index inside the
# gathered view IS the absolute position, so per-request masking is plain
# position arithmetic.
# ---------------------------------------------------------------------------


def paged_update_layer(pool_sl, k_new, v_new, block_tables, positions, active):
    """Scatter new KV for S >= 1 positions per batch row into a pool layer.

    pool_sl: {"k","v": [n_blocks, bs, Hkv, hd], optional "k_scale"/"v_scale"
    [n_blocks, bs, Hkv]}.  k_new/v_new: [B, S, Hkv, hd] — S == 1 is the
    one-token decode step; S == k+1 is the speculative verify step writing a
    whole draft chunk at per-slot position offsets.  positions: [B] (S == 1)
    or [B, S] absolute write positions; active: [B] or [B, S] bool —
    inactive entries scatter out of bounds and are dropped (never corrupting
    live blocks), which is also how verify masks a slot's unused draft tail.
    FP8 pools quantize through the same ``_quant_kv`` as the dense cache
    path, so a paged request's stored values match the static-batch cache
    bit for bit.
    """
    n_blocks, bs = pool_sl["k"].shape[:2]
    if positions.ndim == 1:
        positions = positions[:, None]                # [B] -> [B, 1]
    active = jnp.broadcast_to(active[:, None] if active.ndim == 1 else active,
                              positions.shape)
    blk = jnp.take_along_axis(block_tables, positions // bs, axis=1)  # [B, S]
    blk = jnp.where(active, blk, n_blocks)            # OOB -> dropped
    off = positions % bs
    out = dict(pool_sl)
    if pool_sl.get("k_scale") is not None:
        kq, ks = _quant_kv(k_new)
        vq, vs = _quant_kv(v_new)
        out["k"] = pool_sl["k"].at[blk, off].set(kq, mode="drop")
        out["v"] = pool_sl["v"].at[blk, off].set(vq, mode="drop")
        out["k_scale"] = pool_sl["k_scale"].at[blk, off].set(ks, mode="drop")
        out["v_scale"] = pool_sl["v_scale"].at[blk, off].set(vs, mode="drop")
    else:
        dt = pool_sl["k"].dtype
        out["k"] = pool_sl["k"].at[blk, off].set(k_new.astype(dt),
                                                 mode="drop")
        out["v"] = pool_sl["v"].at[blk, off].set(v_new.astype(dt),
                                                 mode="drop")
    # TP: pages stay KV-head-sharded through the scatter (the block and
    # slot dims are never sharded, so each shard writes its own heads)
    pool_axes = ("blocks", "blockslot", "kv", "headdim")
    return {name: cst(a, pool_axes if a.ndim == 4 else pool_axes[:-1])
            for name, a in out.items()}


def paged_gather_layer(pool_sl, block_tables, dtype=jnp.bfloat16):
    """Gather per-request dense KV views [B, MB*bs, Hkv, hd] from the pool.

    block_tables: [B, MB] pool block ids (entries for unallocated logical
    blocks may be arbitrary in-range ids — callers mask by position).
    """
    b, mb = block_tables.shape
    def dense(name):
        g = pool_sl[name][block_tables]               # [B, MB, bs, ...]
        return g.reshape(b, mb * g.shape[2], *g.shape[3:])
    if pool_sl.get("k_scale") is not None:
        return (_dequant_kv(dense("k"), dense("k_scale"), dtype),
                _dequant_kv(dense("v"), dense("v_scale"), dtype))
    return dense("k").astype(dtype), dense("v").astype(dtype)


def paged_attend(q, pool_sl, block_tables, pos, *, window: int = 0):
    """Decode/verify attention against the paged pool: q [B, S, H, hd].

    ``pos``: per-query valid-key counts (every attended position's KV must
    already be written) — [B] applies one count to every query (the S == 1
    decode step), [B, S] gives each query its own count (the speculative
    verify step passes lens + i + 1 for query i, which IS the causal
    intra-chunk mask: draft position i sees the prompt, the accepted
    history, and drafts 0..i-1, never its successors).  Numerically this is
    ``decode_attend`` with a per-(row, query) validity mask: masked
    positions reach the softmax as exp(-1e30-...) = 0 exactly, so a query's
    probabilities are identical however many pool blocks its table
    addresses and whatever the later draft positions contain — multi-token
    verification reproduces sequential one-token decode per position.
    ``window`` masks by absolute position (the pool keeps every block live
    for simplicity — no ring buffer).
    """
    k, v = paged_gather_layer(pool_sl, block_tables, q.dtype)
    b, s_alloc, hkv, hd = k.shape
    h = q.shape[2]
    # TP: the gathered per-slot views keep the pool's KV-head sharding, and
    # repeat_kv expands each kv head in place, so the repeated heads land on
    # the same shard as their group's q heads — attention is head-local
    k = cst(k, ("batch", "seq", "kv", "none"))
    v = cst(v, ("batch", "seq", "kv", "none"))
    k = cst(repeat_kv(k, h // hkv), ("batch", "seq", "heads", "none"))
    v = cst(repeat_kv(v, h // hkv), ("batch", "seq", "heads", "none"))
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    slot = jnp.arange(s_alloc)
    qpos = pos[:, None] if pos.ndim == 1 else pos     # [B, 1] or [B, S]
    valid = slot[None, None, :] < qpos[:, :, None]    # [B, S(|1), S_alloc]
    if window:
        valid = valid & (slot[None, None, :] >= qpos[:, :, None] - window)
    s = jnp.where(valid[:, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, -1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def paged_attend_fused(q, pool_sl, block_tables, pos, *, window: int = 0):
    """``paged_attend`` through the fused Pallas kernel — page-table gather,
    FP8 dequant, and attend in ONE pass over the block table, no dense
    [B, MB*bs, Hkv, hd] intermediate in HBM.

    Same contract as ``paged_attend`` (its parity oracle: bitwise for BF16
    pools — the kernel defers softmax until the fully-masked score strip is
    resident, so no rescaling reassociation — and per-element-identical FP8
    dequant; on the TPU one-token decode agrees within bf16 rounding, see
    ``kernels.paged_attention``).  Single-device only: a ``pallas_call`` cannot be partitioned
    by GSPMD, so mesh-traced paths keep the gather+attend two-step
    (``serve.engine`` resolves ``fused_kernels="auto"`` accordingly).
    """
    from repro.kernels import ops
    return ops.paged_attention(q, pool_sl, block_tables, pos, window=window)
