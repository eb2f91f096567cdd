"""Decoder-only LM: dense (OLMo/Qwen/Granite/AceReason), MoE (Arctic,
Qwen2-MoE) and VLM-backbone (Qwen2-VL, M-RoPE) families in one scan body.

Functional protocol (shared by all model modules):

    param_specs(cfg)                        -> ParamSpec pytree
    init_params(cfg, rng)                   -> params
    apply(cfg, params, batch, qcfg, output) -> logits | hidden
    unembed(cfg, params)                    -> [d, V]
    init_cache(cfg, batch, s_max, abstract) -> cache pytree
    prefill(cfg, params, batch, qcfg, s_max)-> (logits, cache)
    decode_step(cfg, params, cache, batch, qcfg) -> (logits, cache)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.qconfig import QuantConfig
from repro.distributed.ctx import cst
from repro.obs import numerics as obs_numerics

from . import attention as attn
from . import common, layers


# ---------------------------------------------------------------------------
# parameter specs
# ---------------------------------------------------------------------------


def _norm_specs(cfg, d):
    P = common.ParamSpec
    if cfg.norm == "rmsnorm":
        return {"w": P((d,), ("embed",), init="ones")}
    if cfg.norm == "layernorm":
        return {"w": P((d,), ("embed",), init="ones"),
                "b": P((d,), ("embed",), init="zeros")}
    return {}          # layernorm_np — non-parametric (OLMo)


def run_norm(cfg, p, x):
    return layers.apply_norm(cfg, x, p.get("w"), p.get("b"))


def _layer_specs(cfg):
    P = common.ParamSpec
    d, ff, hd = cfg.d_model, cfg.d_ff, cfg.head_dim
    h, hkv = cfg.n_heads, cfg.n_kv_heads
    spec = {
        "ln1": _norm_specs(cfg, d),
        "wqkv": P((d, cfg.qkv_dim), ("embed", "qkv"), kind="attn"),
        "wo": P((h * hd, d), ("qkv", "embed"), kind="attn", scale=0.5),
        "ln2": _norm_specs(cfg, d),
    }
    if cfg.qkv_bias:
        spec["bqkv"] = P((cfg.qkv_dim,), ("qkv",), init="zeros")
    if cfg.n_experts:
        ffe = cfg.moe_d_ff
        # EP shards the expert dim over "model"; TP shards the expert FFN
        # dim instead (better when the dispatch is data-local — §Perf M4)
        eax = "expert" if cfg.moe_shard == "ep" else "none"
        spec["router"] = P((d, cfg.n_experts), ("embed", "expert"),
                           kind="router")
        spec["moe_wg"] = P((cfg.n_experts, d, ffe), (eax, "embed", "mlp"),
                           kind="mlp", contract_axis=1)
        spec["moe_wu"] = P((cfg.n_experts, d, ffe), (eax, "embed", "mlp"),
                           kind="mlp", contract_axis=1)
        spec["moe_wd"] = P((cfg.n_experts, ffe, d), (eax, "mlp", "embed"),
                           kind="mlp", contract_axis=1, scale=0.5)
        if cfg.shared_d_ff:
            sf = cfg.shared_d_ff
            spec["sh_wg"] = P((d, sf), ("embed", "mlp"), kind="mlp")
            spec["sh_wu"] = P((d, sf), ("embed", "mlp"), kind="mlp")
            spec["sh_wd"] = P((sf, d), ("mlp", "embed"), kind="mlp", scale=0.5)
            spec["sh_gate"] = P((d, 1), ("embed", "none"), kind="router")
        if cfg.moe_dense_residual:
            spec["res_wg"] = P((d, ff), ("embed", "mlp"), kind="mlp")
            spec["res_wu"] = P((d, ff), ("embed", "mlp"), kind="mlp")
            spec["res_wd"] = P((ff, d), ("mlp", "embed"), kind="mlp", scale=0.5)
    else:
        if cfg.mlp == "swiglu":
            spec["wg"] = P((d, ff), ("embed", "mlp"), kind="mlp")
            spec["wu"] = P((d, ff), ("embed", "mlp"), kind="mlp")
            spec["wd"] = P((ff, d), ("mlp", "embed"), kind="mlp", scale=0.5)
        else:
            spec["wi"] = P((d, ff), ("embed", "mlp"), kind="mlp")
            spec["wd"] = P((ff, d), ("mlp", "embed"), kind="mlp", scale=0.5)
    return spec


def param_specs(cfg):
    P = common.ParamSpec
    d, v = cfg.d_model, cfg.vocab_size
    specs = {
        "embed": P((v, d), ("vocab", "embed"), init="embed", kind="embed"),
        "layers": common.stack_specs(_layer_specs(cfg), cfg.n_layers),
        "final_norm": _norm_specs(cfg, d),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P((d, v), ("embed", "vocab"), kind="lm_head",
                             scale=1.0)
    return specs


def init_params(cfg, rng):
    return common.init_params(param_specs(cfg), rng)


def unembed(cfg, params):
    return params["embed"].T if cfg.tie_embeddings else params["lm_head"]


# ---------------------------------------------------------------------------
# layer body
# ---------------------------------------------------------------------------


def _rope(cfg, x, pos):
    if cfg.mrope_sections:
        return layers.apply_mrope(x, pos, cfg.rope_theta, cfg.mrope_sections)
    return layers.apply_rope(x, pos, cfg.rope_theta)


def _attention(qcfg, cfg, p, h, pos, mode, cache_sl, pos_idx):
    b, s, _ = h.shape
    hd, nh, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    qkv = layers.qdense(qcfg, "attn", h, p["wqkv"], p.get("bqkv"),
                        parallelism="column")
    q, k, v = jnp.split(qkv, [nh * hd, (nh + nkv) * hd], axis=-1)
    hax = ("batch", "seq", "heads", "none")
    kax = ("batch", "seq", "kv", "none")
    q = cst(_rope(cfg, attn.split_heads(q, nh, hd), pos), hax)
    k = cst(_rope(cfg, attn.split_heads(k, nkv, hd), pos), kax)
    v = cst(attn.split_heads(v, nkv, hd), kax)

    new_cache = None
    if mode == "decode":
        s_max = cache_sl["k"].shape[1]
        write_at = pos_idx % s_max if cfg.window else pos_idx
        new_cache = attn.cache_update_layer(cache_sl, k, v, write_at)
        out = attn.decode_attend(q, new_cache, pos_idx + 1, window=cfg.window)
    else:
        out = attn.blockwise_attention(q, k, v, causal=True,
                                       window=cfg.window)
        if mode == "prefill":
            new_cache = {"k": k, "v": v}       # collected via scan ys
    out = cst(out, ("batch", "seq", "heads", "none"))
    out = cst(layers.qdense(qcfg, "attn", out.reshape(b, s, nh * hd), p["wo"],
                            parallelism="row"),
              ("batch", "seq", "none"))
    return out, new_cache


def _ffn(qcfg, cfg, p, h):
    if not cfg.n_experts:
        if cfg.mlp == "swiglu":
            return layers.swiglu_mlp(qcfg, h, p["wg"], p["wu"], p["wd"]), {}
        return layers.gelu_mlp(qcfg, h, p["wi"], p["wd"]), {}
    out, aux = layers.moe_ffn(qcfg, cfg, h, p["router"],
                              p["moe_wg"], p["moe_wu"], p["moe_wd"])
    if cfg.shared_d_ff:
        sh = layers.swiglu_mlp(qcfg, h, p["sh_wg"], p["sh_wu"], p["sh_wd"])
        gate = jax.nn.sigmoid(
            layers.qdense(qcfg, "router", h, p["sh_gate"]).astype(jnp.float32))
        out = out + (sh.astype(jnp.float32) * gate).astype(out.dtype)
    if cfg.moe_dense_residual:
        out = out + layers.swiglu_mlp(qcfg, h, p["res_wg"], p["res_wu"],
                                      p["res_wd"])
    return out, aux


def _block(qcfg, cfg, p, x, pos, mode, cache_sl, pos_idx):
    h = run_norm(cfg, p["ln1"], x)
    a, new_cache = _attention(qcfg, cfg, p, h, pos, mode, cache_sl, pos_idx)
    x = x + a
    h = run_norm(cfg, p["ln2"], x)
    f, aux = _ffn(qcfg, cfg, p, h)
    x = x + f
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# forward passes
# ---------------------------------------------------------------------------


def _embed_inputs(cfg, params, batch):
    x = params["embed"][batch["tokens"]]
    if cfg.mrope_sections and "vis_embeds" in batch:
        # VLM: splice precomputed patch embeddings (frontend is a stub)
        m = batch["vis_mask"][..., None]
        x = jnp.where(m, batch["vis_embeds"].astype(x.dtype), x)
    return x


def _positions(cfg, batch, s, offset=0):
    if cfg.mrope_sections:
        return batch["pos3"]                    # [B, S, 3]
    b = batch["tokens"].shape[0]
    return jnp.broadcast_to(jnp.arange(s) + offset, (b, s))


def apply(cfg, params, batch, qcfg: QuantConfig, output: str = "logits"):
    """Teacher-forcing forward: [B,S] tokens -> [B,S,V] logits."""
    x = cst(_embed_inputs(cfg, params, batch), ("batch", "seq", "none"))
    pos = _positions(cfg, batch, x.shape[1])

    def body(qc):
        def fn(carry, inp):
            p, _ = inp
            y, _, aux = _block(qc, cfg, p, carry, pos, "train", None, None)
            y = cst(y, ("batch", "seq", "none"))
            if qc.numerics:
                # per-layer hidden-state tap: scan_layers stacks these
                # into [n_layers, B, S, d] for teacher-student geometry
                tape = obs_numerics.active()
                if tape is not None:
                    tape.put("hidden", {"h": y})
            return y, aux
        return fn

    x, _ = common.scan_layers(body, x, params["layers"], None, qcfg,
                              qcfg.skip_first_layers, qcfg.skip_last_layers,
                              cfg.remat)
    x = run_norm(cfg, params["final_norm"], x)
    if output == "hidden":
        return x
    w = unembed(cfg, params)
    return cst(layers.qdense(qcfg, "lm_head", x, w, parallelism="column"),
               ("batch", "seq", "vocab"))


def cache_specs(cfg, batch_size, s_max):
    P = common.ParamSpec
    s_alloc = min(s_max, cfg.window) if cfg.window else s_max
    fp8 = _kv_fp8(cfg)
    kdt = jnp.float8_e4m3fn if fp8 else jnp.bfloat16
    shape = (cfg.n_layers, batch_size, s_alloc, cfg.n_kv_heads, cfg.head_dim)
    axes = ("layers", "batch", "seq", "kv", "headdim")
    c = {"k": P(shape, axes, dtype=kdt, init="zeros"),
         "v": P(shape, axes, dtype=kdt, init="zeros"),
         "pos": P((), (), dtype=jnp.int32, init="zeros")}
    if fp8:
        c["k_scale"] = P(shape[:-1], axes[:-1], dtype=jnp.float32, init="zeros")
        c["v_scale"] = P(shape[:-1], axes[:-1], dtype=jnp.float32, init="zeros")
    return c


def init_cache(cfg, batch_size, s_max):
    return common.zeros_from_specs(cache_specs(cfg, batch_size, s_max))


def _kv_fp8(cfg):
    return cfg.quant_recipe == "moe_hybrid"


def _cache_slices(cache):
    return {k: v for k, v in cache.items() if k != "pos"}


def decode_step(cfg, params, cache, batch, qcfg: QuantConfig):
    """One-token decode: batch["tokens"] [B,1] against the cache."""
    x = _embed_inputs(cfg, params, batch)
    pos_idx = cache["pos"]
    if cfg.mrope_sections:
        pos = batch["pos3"]                      # [B,1,3]
    else:
        pos = jnp.full((x.shape[0], 1), pos_idx, jnp.int32)

    def body(qc):
        def fn(carry, inp):
            p, csl = inp
            y, new_c, _ = _block(qc, cfg, p, carry, pos, "decode", csl, pos_idx)
            return y, new_c
        return fn

    x, new_cache = common.scan_layers(
        body, x, params["layers"], _cache_slices(cache), qcfg,
        qcfg.skip_first_layers, qcfg.skip_last_layers, "none")
    x = run_norm(cfg, params["final_norm"], x)
    logits = cst(layers.qdense(qcfg, "lm_head", x, unembed(cfg, params),
                          parallelism="column"),
                 ("batch", "none", "vocab"))
    new_cache["pos"] = pos_idx + 1
    return logits, new_cache


def _attention_slots(qcfg, cfg, p, h, lens, active, cache_sl):
    """Per-row decode attention against a dense [B, S_alloc, ...] cache.

    The slot-state engine batches requests at independent positions:
    ``lens`` [B] is each row's cached-token count (== this token's absolute
    position), ``active`` [B] masks rows with no work (their cache writes
    are dropped).  Numerically this is the scalar decode branch of
    ``_attention`` row by row — per-row RoPE, ring writes at
    ``lens % S_alloc`` for windowed layers, and per-row validity masks —
    so an active row is bitwise equal to a batch-1 ``decode_step``.
    """
    b, s, _ = h.shape
    hd, nh, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    qkv = layers.qdense(qcfg, "attn", h, p["wqkv"], p.get("bqkv"),
                        parallelism="column")
    q, k, v = jnp.split(qkv, [nh * hd, (nh + nkv) * hd], axis=-1)
    pos = lens[:, None]                               # [B, 1]
    hax = ("batch", "seq", "heads", "none")
    kax = ("batch", "seq", "kv", "none")
    q = cst(_rope(cfg, attn.split_heads(q, nh, hd), pos), hax)
    k = cst(_rope(cfg, attn.split_heads(k, nkv, hd), pos), kax)
    v = cst(attn.split_heads(v, nkv, hd), kax)
    s_max = cache_sl["k"].shape[1]
    write_at = lens % s_max if cfg.window else lens
    new_cache = attn.cache_update_slots(cache_sl, k, v, write_at, active)
    out = attn.decode_attend(q, new_cache, lens + 1, window=cfg.window)
    out = cst(out, hax)
    out = cst(layers.qdense(qcfg, "attn", out.reshape(b, s, nh * hd), p["wo"],
                            parallelism="row"),
              ("batch", "seq", "none"))
    return out, new_cache


def _block_slots(qcfg, cfg, p, x, lens, active, cache_sl):
    """Transformer layer for the slot-state decode step (per-row positions)."""
    h = run_norm(cfg, p["ln1"], x)
    a, new_cache = _attention_slots(qcfg, cfg, p, h, lens, active, cache_sl)
    x = x + a
    h = run_norm(cfg, p["ln2"], x)
    f, aux = _ffn(qcfg, cfg, p, h)
    x = x + f
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# paged-pool forwards (continuous-batching engine, repro.serve)
# ---------------------------------------------------------------------------


def paged_pool_specs(cfg, n_blocks: int, block_size: int):
    """ParamSpecs for the block-granular KV pool shared by all requests.

    K and V pages are [L, n_blocks, block_size, Hkv*hd]: the layout the
    fused attention kernel reads, so a page's heads lie side by side on the
    lanes and no step ever relayouts the pool (a separate [.., Hkv, hd]
    minor pair is tiled differently on the TPU at hd 64).  The flat head
    axis is row-major, so a head's hd lanes stay together and TP shards on
    "kv" stay head-local wherever the TP degree divides Hkv.  FP8 pools
    (moe_hybrid recipe) carry per-(slot, head) fp32 scales
    [L, n_blocks, block_size, Hkv] next to the E4M3 pages, exactly like
    the dense cache.  Also used abstractly by the dry-run to price the
    pool.
    """
    P = common.ParamSpec
    fp8 = _kv_fp8(cfg)
    kdt = jnp.float8_e4m3fn if fp8 else jnp.bfloat16
    lead = (cfg.n_layers, n_blocks, block_size)
    c = {"k": P((*lead, cfg.n_kv_heads * cfg.head_dim), attn.POOL_AXES,
                dtype=kdt, init="zeros"),
         "v": P((*lead, cfg.n_kv_heads * cfg.head_dim), attn.POOL_AXES,
                dtype=kdt, init="zeros")}
    if fp8:
        c["k_scale"] = P((*lead, cfg.n_kv_heads), attn.POOL_AXES,
                         dtype=jnp.float32, init="zeros")
        c["v_scale"] = P((*lead, cfg.n_kv_heads), attn.POOL_AXES,
                         dtype=jnp.float32, init="zeros")
    return c


def init_paged_pool(cfg, n_blocks: int, block_size: int):
    return common.zeros_from_specs(paged_pool_specs(cfg, n_blocks, block_size))


def prefill_scratch_specs(cfg, s_alloc: int):
    """BF16 per-layer KV scratch for one request's chunked prefill.

    Chunked prefill must attend the BF16 prompt prefix (whole-prompt prefill
    quantizes the cache only AFTER blockwise attention ran on BF16 KV), so
    the in-flight request keeps its prefix here; the pool gets the
    (possibly FP8) copy for later decode reads.
    """
    P = common.ParamSpec
    shape = (cfg.n_layers, 1, s_alloc, cfg.n_kv_heads, cfg.head_dim)
    axes = ("layers", "batch", "seq", "kv", "headdim")
    return {"k": P(shape, axes, dtype=jnp.bfloat16, init="zeros"),
            "v": P(shape, axes, dtype=jnp.bfloat16, init="zeros")}


def write_prompt_to_pool(pool, cache, block_ids):
    """Scatter a batch=1 ``prefill`` cache (logical length P) into pool blocks.

    ``cache``: the dict ``prefill(..., s_max=None)`` returns, minus "pos";
    ``block_ids``: [ceil(P / block_size)] pool block ids.  Tail positions of
    the last block are zero-filled (masked by the request length at read).
    The cache's [.., Hkv, hd] rows are reshaped to the pool's flat head
    axis on the way (the prompt's rows, never the pool).
    """
    bs = pool["k"].shape[2]
    out = dict(pool)
    ids = jnp.asarray(block_ids, jnp.int32)
    for name in [k for k in pool if k in cache]:
        c = cache[name]                               # [L, 1, P, ...]
        l, _, p_len = c.shape[:3]
        pad = (-p_len) % bs
        if pad:
            c = jnp.pad(c, [(0, 0), (0, 0), (0, pad)]
                        + [(0, 0)] * (c.ndim - 3))
        blocks = c[:, 0].reshape(l, (p_len + pad) // bs, bs,
                                 pool[name].shape[3])
        out[name] = pool[name].at[:, ids].set(blocks.astype(pool[name].dtype))
    return out


def _attention_paged(qcfg, cfg, p, h, pos, pool, layer, block_tables,
                     positions, active, fused: bool = False):
    """Paged attention for S >= 1 new positions per slot, against layer
    ``layer`` of the whole (carried) pool; returns (out, new_pool).

    ``positions``: [B] (one-token decode) or [B, S] (multi-token verify)
    absolute write positions — RoPE ``pos`` must address the same positions;
    ``active``: [B] or [B, S] write mask.  Each query attends positions
    < its own position + 1 (causal within the new chunk).

    ``fused`` routes the gather+attend through the one-pass Pallas kernel
    (``attn.paged_attend_fused``); the two-step stays as its parity oracle
    and as the mesh path (the engine only enables fusion meshless).

    Under a TP mesh the whole block is head-local: q shards on "heads", new
    k/v and the pool pages on "kv" (same shards — GQA groups never split),
    so paged update + gather + attend run without collectives; the only
    cross-shard traffic is the row-parallel ``wo`` psum.
    """
    b, s, _ = h.shape
    hd, nh, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    qkv = layers.qdense(qcfg, "attn", h, p["wqkv"], p.get("bqkv"),
                        parallelism="column")
    q, k, v = jnp.split(qkv, [nh * hd, (nh + nkv) * hd], axis=-1)
    hax = ("batch", "seq", "heads", "none")
    kax = ("batch", "seq", "kv", "none")
    q = cst(_rope(cfg, attn.split_heads(q, nh, hd), pos), hax)
    k = cst(_rope(cfg, attn.split_heads(k, nkv, hd), pos), kax)
    v = cst(attn.split_heads(v, nkv, hd), kax)
    pool = attn.paged_update_layer(pool, layer, k, v, block_tables,
                                   positions, active)
    attend = attn.paged_attend_fused if fused else attn.paged_attend
    out = cst(attend(q, pool, layer, block_tables, positions + 1,
                     window=cfg.window), hax)
    out = cst(layers.qdense(qcfg, "attn", out.reshape(b, s, nh * hd), p["wo"],
                            parallelism="row"),
              ("batch", "seq", "none"))
    return out, pool


def decode_step_paged(cfg, params, pool, block_tables, lens, active, batch,
                      qcfg: QuantConfig, fused: bool = False):
    """One-token decode for a slot batch against the paged KV pool.

    batch["tokens"]: [n_slots, 1]; block_tables: [n_slots, MB] pool block
    ids; lens: [n_slots] cached-token counts; active: [n_slots] bool.
    Inactive slots compute garbage logits (the engine ignores them) but
    their pool writes are dropped, so live blocks are never corrupted.
    ``fused`` (static) selects the one-pass fused paged-attention kernel.
    The whole pool rides in the layer scan's carry and each layer writes
    and reads it at its own index (``scan_layers``' carried-state form), so
    a donated pool is updated in place: no layer slice, no second pool.
    Returns (logits [n_slots, 1, V], new_pool).
    """
    if cfg.mrope_sections:
        raise NotImplementedError("paged decode does not support M-RoPE")
    x = _embed_inputs(cfg, params, batch)
    return _paged_layers(cfg, params, pool, x, lens[:, None], block_tables,
                         lens, active, qcfg, fused)


def verify_step_paged(cfg, params, pool, block_tables, lens, active, n_prop,
                      batch, qcfg: QuantConfig, fused: bool = False):
    """Multi-token speculative verification: score k+1 positions at once.

    batch["tokens"]: [n_slots, K1] where row token 0 is the slot's last
    emitted token and tokens 1..n_prop[b] are draft proposals (the tail is
    padding).  block_tables: [n_slots, MB]; lens: [n_slots] cached-token
    counts; active: [n_slots] bool; n_prop: [n_slots] proposed-draft counts
    (0 <= n_prop <= K1-1 — a row with n_prop == 0 degenerates to the plain
    one-token decode step).

    KV for every fed position (lens + i, i <= n_prop) is written to the
    pool; query i attends positions < lens + i + 1 (causal intra-chunk
    masks via per-slot position offsets).  Row positions beyond n_prop
    neither write KV nor influence live positions — their logits are
    garbage the caller must ignore.  The caller is responsible for
    rolling back rejected positions (they stay invalidated as long as the
    slot's length accounting only advances by ACCEPTED tokens; the next
    verify step overwrites them).

    For token-for-token parity with sequential ``decode_step_paged`` the
    serving config must use ``act_scope="token"`` (per-position activation
    scales) and, for MoE archs, ``moe_dispatch="token"`` — with those, the
    logits at position i are exactly what a one-token decode conditioned on
    the same prefix would produce.

    Returns (logits [n_slots, K1, V], new_pool).
    """
    if cfg.mrope_sections:
        raise NotImplementedError("paged verify does not support M-RoPE")
    x = _embed_inputs(cfg, params, batch)
    k1 = x.shape[1]
    offs = jnp.arange(k1)
    positions = lens[:, None] + offs[None, :]          # [n_slots, K1]
    tok_active = active[:, None] & (offs[None, :] <= n_prop[:, None])
    return _paged_layers(cfg, params, pool, x, positions, block_tables,
                         positions, tok_active, qcfg, fused)


def _paged_layers(cfg, params, pool, x, pos, block_tables, positions, active,
                  qcfg, fused):
    """The layer scan of decode and verify, then the head: the pool rides
    in the carry and each layer writes and reads it at its own index."""
    def body(qc):
        def fn(carry, inp):
            (x, pool), (p, layer) = carry, inp
            h = run_norm(cfg, p["ln1"], x)
            a, pool = _attention_paged(qc, cfg, p, h, pos, pool, layer,
                                       block_tables, positions, active,
                                       fused=fused)
            y = x + a
            h = run_norm(cfg, p["ln2"], y)
            f, _ = _ffn(qc, cfg, p, h)
            return (y + f, pool), None
        return fn

    (x, new_pool), _ = common.scan_layers(
        body, (x, pool), params["layers"], jnp.arange(cfg.n_layers), qcfg,
        qcfg.skip_first_layers, qcfg.skip_last_layers, "none")
    x = run_norm(cfg, params["final_norm"], x)
    logits = layers.qdense(qcfg, "lm_head", x, unembed(cfg, params),
                           parallelism="column")
    return logits, new_pool


def _attention_prefill_chunk(qcfg, cfg, p, h, pos, scratch, pool, layer, bt,
                             positions, tok_active, start, n_valid):
    b, c, _ = h.shape                                 # b == 1
    hd, nh, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    qkv = layers.qdense(qcfg, "attn", h, p["wqkv"], p.get("bqkv"),
                        parallelism="column")
    q, k, v = jnp.split(qkv, [nh * hd, (nh + nkv) * hd], axis=-1)
    q = cst(_rope(cfg, attn.split_heads(q, nh, hd), pos),
            ("batch", "seq", "heads", "none"))
    k = cst(_rope(cfg, attn.split_heads(k, nkv, hd), pos),
            ("batch", "seq", "kv", "none"))
    v = cst(attn.split_heads(v, nkv, hd), ("batch", "seq", "kv", "none"))
    # the scratch is carried whole too: written in place at [layer, 0, start]
    scratch = {name: jax.lax.dynamic_update_slice(
        scratch[name], x[None].astype(scratch[name].dtype),
        (layer, 0, start, 0, 0)) for name, x in (("k", k), ("v", v))}
    out = attn.blockwise_attention(q, scratch["k"][layer], scratch["v"][layer],
                                   causal=True, window=cfg.window,
                                   q_offset=start, kv_valid=start + n_valid)
    # pool copy (FP8 pools quantize here) for later decode reads; one write
    # per chunk token, pad tokens dropped
    pool = attn.paged_update_layer(pool, layer, k.swapaxes(0, 1),
                                   v.swapaxes(0, 1), bt, positions,
                                   tok_active)
    out = cst(layers.qdense(qcfg, "attn", out.reshape(b, c, nh * hd), p["wo"],
                            parallelism="row"),
              ("batch", "seq", "none"))
    return out, scratch, pool


def prefill_chunk_paged(cfg, params, scratch, pool, block_table, start,
                        n_valid, batch, qcfg: QuantConfig):
    """Prefill one fixed-size prompt chunk for a single request.

    batch["tokens"]: [1, C] (the chunk, right-padded past ``n_valid``);
    ``scratch``: BF16 prefix KV (see ``prefill_scratch_specs``);
    ``block_table``: [MB] this request's pool blocks; ``start``: tokens
    already prefilled (traced); ``n_valid``: valid tokens in this chunk
    (traced, 1..C).  Returns (logits at the last valid position [1, 1, V],
    new_scratch, new_pool).  Scratch and pool both ride in the layer scan's
    carry and are written in place at each layer's index.  Shapes are
    static across chunks and requests, so the engine compiles this once per
    chunk size.
    """
    if cfg.mrope_sections:
        raise NotImplementedError("paged prefill does not support M-RoPE")
    x = _embed_inputs(cfg, params, batch)
    c = x.shape[1]
    pos = (jnp.arange(c) + start)[None, :]            # [1, C]
    positions = start + jnp.arange(c)                 # [C] pool positions
    tok_active = jnp.arange(c) < n_valid
    bt = jnp.broadcast_to(block_table[None, :], (c, block_table.shape[0]))

    def body(qc):
        def fn(carry, inp):
            (x, scratch, pool), (p, layer) = carry, inp
            h = run_norm(cfg, p["ln1"], x)
            a, scratch, pool = _attention_prefill_chunk(
                qc, cfg, p, h, pos, scratch, pool, layer, bt, positions,
                tok_active, start, n_valid)
            y = x + a
            h = run_norm(cfg, p["ln2"], y)
            f, _ = _ffn(qc, cfg, p, h)
            return (y + f, scratch, pool), None
        return fn

    (x, new_scratch, new_pool), _ = common.scan_layers(
        body, (x, scratch, pool), params["layers"],
        jnp.arange(cfg.n_layers), qcfg, qcfg.skip_first_layers,
        qcfg.skip_last_layers, "none")
    x = run_norm(cfg, params["final_norm"], x)
    x_last = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)
    logits = layers.qdense(qcfg, "lm_head", x_last, unembed(cfg, params),
                           parallelism="column")
    return logits, new_scratch, new_pool


def prefill(cfg, params, batch, qcfg: QuantConfig, s_max: int | None = None):
    """Prompt pass: returns (last-token logits, populated cache)."""
    x = _embed_inputs(cfg, params, batch)
    s = x.shape[1]
    pos = _positions(cfg, batch, s)

    def body(qc):
        def fn(carry, inp):
            p, _ = inp
            y, kv, _ = _block(qc, cfg, p, carry, pos, "prefill", None, None)
            if _kv_fp8(cfg):
                kq, ks = attn._quant_kv(kv["k"])
                vq, vs = attn._quant_kv(kv["v"])
                kv = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
            return y, kv
        return fn

    x, kv = common.scan_layers(body, x, params["layers"], None, qcfg,
                               qcfg.skip_first_layers, qcfg.skip_last_layers,
                               cfg.remat)
    x = run_norm(cfg, params["final_norm"], x)
    logits = layers.qdense(qcfg, "lm_head", x[:, -1:], unembed(cfg, params),
                           parallelism="column")

    cache = dict(kv)
    if cfg.window and s > cfg.window:
        # keep the last `window` positions, ring-aligned: slot p % window
        # holds position p (decode continues writing at pos % window)
        w = cfg.window
        cache = jax.tree.map(
            lambda a: jnp.roll(a[:, :, s - w:], s % w, axis=2), cache)
    elif s_max:
        s_alloc = min(s_max, cfg.window) if cfg.window else s_max
        if s_alloc > s:
            cache = jax.tree.map(
                lambda a: jnp.pad(a, [(0, 0), (0, 0), (0, s_alloc - s)]
                                  + [(0, 0)] * (a.ndim - 3)), cache)
    cache["pos"] = jnp.asarray(s, jnp.int32)
    return logits, cache
