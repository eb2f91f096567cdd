"""Fused serving-kernel tier vs its gather+dequant parity oracles.

The fused paged-attention kernel must be BITWISE identical to the
``paged_gather_layer`` -> ``paged_attend`` two-step (the deferred-exact-
softmax design: scores and dequantized V pages accumulate in VMEM scratch
and the softmax+PV runs once, in the oracle's op order).  The grouped
NVFP4 GEMM must be bitwise identical to per-group runs of the 2-D kernel,
and the lane128 scale swizzle must not change a single bit.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import nvfp4
from repro.core.qconfig import QuantConfig
from repro.kernels import ops
from repro.kernels.nvfp4_matmul import nvfp4_matmul, nvfp4_matmul_grouped
from repro.models import attention as attn
from repro.models import layers


def _bitwise(got, want):
    # f32 upcast of bf16 is injective, so f32 equality == bf16 bit equality
    return np.array_equal(np.asarray(got, np.float32),
                          np.asarray(want, np.float32))


# layers in a test pool, and the one the attend cases read: every layer
# holds different values, so reading the wrong one cannot pass
N_LAYERS, LAYER = 3, 1


def _pool(key, n_blocks, bs, hkv, hd, fp8=False):
    """A whole pool in the stored layout: pages [L, n_blocks, bs, Hkv*hd],
    FP8 scales [L, n_blocks, bs, Hkv]."""
    shape = (N_LAYERS, n_blocks, bs, hkv, hd)
    k = jax.random.normal(key, shape, jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 1), shape, jnp.float32)
    flat = lambda x: x.reshape(*shape[:3], hkv * hd)
    if fp8:
        kq = nvfp4.fp8_quantize(k, axis=-1)
        vq = nvfp4.fp8_quantize(v, axis=-1)
        return {"k": flat(kq.values), "v": flat(vq.values),
                "k_scale": kq.scale[..., 0], "v_scale": vq.scale[..., 0]}
    return {"k": flat(k).astype(jnp.bfloat16),
            "v": flat(v).astype(jnp.bfloat16)}


def _case(key, b, mb, bs, hkv, n_rep, hd, s_q=1, fp8=False):
    """Pool + block tables + per-query positions + q for one attend case."""
    n_blocks = b * mb + 2
    pool = _pool(key, n_blocks, bs, hkv, hd, fp8=fp8)
    bt = jax.random.permutation(jax.random.fold_in(key, 2), n_blocks
                                )[: b * mb].reshape(b, mb).astype(jnp.int32)
    # per-slot valid-key counts; verify (s_q > 1) scores consecutive
    # positions, mirroring decoder.verify_step_paged's pos arithmetic
    base = jax.random.randint(jax.random.fold_in(key, 3), (b,), s_q,
                              mb * bs + 1)
    pos = base if s_q == 1 else (base[:, None] - s_q + 1
                                 + jnp.arange(s_q)[None, :]).astype(jnp.int32)
    q = jax.random.normal(jax.random.fold_in(key, 4),
                          (b, s_q, hkv * n_rep, hd)).astype(jnp.bfloat16)
    return q, pool, bt, pos


# ---------------------------------------------------------------------------
# fused paged attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b,mb,bs,hkv,n_rep,hd",
                         [(3, 4, 16, 2, 4, 64),     # GQA decode
                          (2, 2, 8, 4, 1, 32),      # MHA, small pages
                          (1, 8, 16, 1, 2, 128),    # single slot, deep table
                          (4, 3, 16, 3, 2, 48)])    # odd head count
def test_fused_attend_decode_bitwise_bf16(b, mb, bs, hkv, n_rep, hd):
    q, pool, bt, pos = _case(jax.random.PRNGKey(b + mb + hd), b, mb, bs,
                             hkv, n_rep, hd)
    got = attn.paged_attend_fused(q, pool, LAYER, bt, pos)
    want = attn.paged_attend(q, pool, LAYER, bt, pos)
    assert got.dtype == want.dtype == jnp.bfloat16
    assert _bitwise(got, want)


@pytest.mark.parametrize("s_q", [2, 4, 5])
def test_fused_attend_verify_multiquery_bitwise(s_q):
    """q_len = k+1 (speculative verify): per-position causal masks must
    reproduce sequential one-token decode bitwise."""
    q, pool, bt, pos = _case(jax.random.PRNGKey(40 + s_q), 3, 4, 16, 2, 2,
                             64, s_q=s_q)
    got = attn.paged_attend_fused(q, pool, LAYER, bt, pos)
    want = attn.paged_attend(q, pool, LAYER, bt, pos)
    assert _bitwise(got, want)


@pytest.mark.parametrize("window", [8, 16, 40])
@pytest.mark.parametrize("s_q", [1, 3])
def test_fused_attend_window_matches_oracle(window, s_q):
    """Sliding-window masks (ring-buffer / local-attention state plans)
    agree with ``paged_attend(window=...)`` for decode AND verify shapes."""
    q, pool, bt, pos = _case(jax.random.PRNGKey(7 + window), 2, 4, 16, 2, 2,
                             64, s_q=s_q)
    got = attn.paged_attend_fused(q, pool, LAYER, bt, pos, window=window)
    want = attn.paged_attend(q, pool, LAYER, bt, pos, window=window)
    assert _bitwise(got, want)
    if window < 40:
        # the window must actually bite: unwindowed output differs
        assert not _bitwise(got, attn.paged_attend(q, pool, LAYER, bt, pos))


@pytest.mark.parametrize("s_q", [1, 4])
def test_fused_attend_fp8_pool(s_q):
    """FP8 pools: the kernel dequantizes per (token, head) exactly as
    ``_dequant_kv`` (f32 scale multiply, one rounding to bf16), so the
    fused output is per-element identical to the oracle."""
    q, pool, bt, pos = _case(jax.random.PRNGKey(60 + s_q), 3, 3, 16, 2, 3,
                             64, s_q=s_q, fp8=True)
    got = attn.paged_attend_fused(q, pool, LAYER, bt, pos)
    want = attn.paged_attend(q, pool, LAYER, bt, pos)
    assert _bitwise(got, want)


def test_fused_attend_ignores_dead_table_tail():
    """Positions past ``pos`` must not influence the output, whatever the
    unwritten pages hold — poison the tail blocks and re-check."""
    q, pool, bt, pos = _case(jax.random.PRNGKey(5), 2, 4, 8, 2, 2, 32)
    pos = jnp.minimum(pos, 9)                      # keep >3 blocks dead
    want = attn.paged_attend_fused(q, pool, LAYER, bt, pos)
    poisoned = dict(pool)
    live = np.zeros(pool["k"].shape[1], bool)
    live[np.asarray(bt[:, :2]).ravel()] = True     # blocks holding pos < 16
    noise = (1e3 * jax.random.normal(jax.random.PRNGKey(6), pool["k"].shape)
             ).astype(pool["k"].dtype)
    dead = ~jnp.asarray(live)[None, :, None, None]
    poisoned["k"] = jnp.where(dead, noise, pool["k"])
    poisoned["v"] = jnp.where(dead, noise, pool["v"])
    assert _bitwise(attn.paged_attend_fused(q, poisoned, LAYER, bt, pos), want)


@pytest.mark.parametrize("fp8", [False, True], ids=["bf16", "fp8"])
@pytest.mark.parametrize("s_q", [1, 4], ids=["decode", "verify_k3"])
def test_fused_attend_reads_the_layer_operand(s_q, fp8):
    """The kernel's layer operand picks its layer out of the whole pool:
    at every layer it matches the two-step oracle reading the same layer,
    and that layer alone as a one-layer pool; layers hold different
    values, so the outputs differ from layer to layer."""
    q, pool, bt, pos = _case(jax.random.PRNGKey(80 + s_q), 3, 4, 16, 2, 2,
                             64, s_q=s_q, fp8=fp8)
    fused = jax.jit(attn.paged_attend_fused)
    outs = []
    for layer in range(N_LAYERS):
        got = fused(q, pool, jnp.int32(layer), bt, pos)
        assert _bitwise(got, attn.paged_attend(q, pool, layer, bt, pos))
        alone = {k: v[layer:layer + 1] for k, v in pool.items()}
        assert _bitwise(got, attn.paged_attend_fused(q, alone, 0, bt, pos))
        outs.append(got)
    for i in range(N_LAYERS):
        for j in range(i):
            assert not _bitwise(outs[i], outs[j])


# ---------------------------------------------------------------------------
# grouped NVFP4 GEMM
# ---------------------------------------------------------------------------


def _packed_stack(key, g, k, n, n_lead=1):
    w = jax.random.normal(key, (g, k, n), jnp.float32)
    return w, nvfp4.pack(jnp.swapaxes(w, 1, 2), n_lead=n_lead)


@pytest.mark.parametrize("g,m,k,n", [(4, 8, 64, 48), (2, 1, 256, 320),
                                     (8, 7, 96, 40), (3, 16, 512, 128)])
def test_grouped_matmul_bitwise_vs_per_group_kernel(g, m, k, n):
    key = jax.random.PRNGKey(g + m + k)
    x = jax.random.normal(jax.random.fold_in(key, 9), (g, m, k), jnp.float32)
    w, p = _packed_stack(key, g, k, n)
    got = nvfp4_matmul_grouped(x, p, tile_m=32, tile_n=64, tile_k=64,
                               out_dtype=jnp.float32)
    for gi in range(g):
        want = nvfp4_matmul(x[gi], ops.pack_weight(w[gi]), tile_m=32,
                            tile_n=64, tile_k=64, out_dtype=jnp.float32)
        assert _bitwise(got[gi], want), f"group {gi} diverges"


def test_grouped_matmul_vs_dequant_einsum():
    g, m, k, n = 4, 6, 128, 96
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(jax.random.fold_in(key, 9), (g, m, k), jnp.float32)
    _, p = _packed_stack(key, g, k, n)
    got = nvfp4_matmul_grouped(x, p, out_dtype=jnp.float32)
    # the kernel rounds dequantized weight tiles to BF16 (the MXU operand
    # precision) before the dot — mirror that in the reference
    wd = ops.dequant_weight(p, contract_axis=1).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(jnp.einsum("gmk,gkn->gmn", x, wd)),
                               rtol=1e-4, atol=1e-3)


def test_grouped_matmul_shared_tensor_scale_broadcasts():
    """n_lead=0 stacks carry ONE whole-stack tensor scale; the grouped
    kernel must broadcast it per group, matching the dequant fallback."""
    g, m, k, n = 3, 5, 64, 48
    key = jax.random.PRNGKey(17)
    x = jax.random.normal(jax.random.fold_in(key, 9), (g, m, k), jnp.float32)
    w = jax.random.normal(key, (g, k, n), jnp.float32)
    p = nvfp4.pack(jnp.swapaxes(w, 1, 2), n_lead=0)
    assert p.tensor_scale.size == 1
    got = nvfp4_matmul_grouped(x, p, out_dtype=jnp.float32)
    wd = ops.dequant_weight(p, contract_axis=1).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(jnp.einsum("gmk,gkn->gmn", x, wd)),
                               rtol=1e-4, atol=1e-3)


def test_moe_grouped_qeinsum_dispatch_matches_dequant():
    """The qeinsum seam: packed_backend="grouped" routes 3-D MoE stacks
    through the grouped kernel; its output must match the dequant-einsum
    backend bitwise (both dequantize to the same bf16 grid)."""
    e, c, k, n = 4, 3, 64, 48
    key = jax.random.PRNGKey(23)
    x = jax.random.normal(key, (2, e, c, k)).astype(jnp.bfloat16)
    _, p = _packed_stack(jax.random.fold_in(key, 1), e, k, n)
    out = {}
    for backend in ("grouped", "dequant"):
        qcfg = QuantConfig(quantize_weights=False, quantize_activations=False,
                           packed_backend=backend)
        out[backend] = layers.qeinsum(qcfg, "mlp", layers._MOE_EQ, x, p,
                                      contract_axis=1)
    assert out["grouped"].shape == (2, e, c, n)
    assert _bitwise(out["grouped"], out["dequant"])


# ---------------------------------------------------------------------------
# lane128 scale swizzle (Mosaic-lowering layout)
# ---------------------------------------------------------------------------


def test_scale_swizzle_bitwise_2d():
    key = jax.random.PRNGKey(31)
    m, k, n = 16, 512, 128
    x = jax.random.normal(key, (m, k), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (k, n), jnp.float32)
    p = ops.pack_weight(w)
    compact = nvfp4_matmul(x, p, scale_layout="compact",
                           out_dtype=jnp.float32)
    lane128 = nvfp4_matmul(x, p, scale_layout="lane128",
                           out_dtype=jnp.float32)
    assert _bitwise(compact, lane128)


def test_scale_swizzle_bitwise_grouped():
    key = jax.random.PRNGKey(37)
    g, m, k, n = 3, 8, 256, 64
    x = jax.random.normal(key, (g, m, k), jnp.float32)
    _, p = _packed_stack(jax.random.fold_in(key, 1), g, k, n)
    compact = nvfp4_matmul_grouped(x, p, scale_layout="compact",
                                   out_dtype=jnp.float32)
    lane128 = nvfp4_matmul_grouped(x, p, scale_layout="lane128",
                                   out_dtype=jnp.float32)
    assert _bitwise(compact, lane128)


# ---------------------------------------------------------------------------
# interpret_default() env override
# ---------------------------------------------------------------------------


def test_interpret_default_env_override(monkeypatch):
    ops.interpret_default.cache_clear()
    try:
        monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
        auto = ops.interpret_default()
        assert auto == (jax.default_backend() != "tpu")
        for env, want in (("1", True), ("0", False)):
            ops.interpret_default.cache_clear()
            monkeypatch.setenv("REPRO_PALLAS_INTERPRET", env)
            assert ops.interpret_default() is want   # override beats probe
        ops.interpret_default.cache_clear()
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "yes")
        with pytest.raises(ValueError, match="REPRO_PALLAS_INTERPRET"):
            ops.interpret_default()
    finally:
        ops.interpret_default.cache_clear()


def test_interpret_default_is_cached(monkeypatch):
    ops.interpret_default.cache_clear()
    try:
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
        assert ops.interpret_default() is True
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
        assert ops.interpret_default() is True       # cached probe sticks
    finally:
        ops.interpret_default.cache_clear()


# ---------------------------------------------------------------------------
# engine integration: fused on == gather+dequant, token for token
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch,fp8", [("qwen1.5-0.5b", False)])
def test_engine_fused_greedy_matches_unfused(arch, fp8):
    from repro import configs
    from repro.launch import serve
    from repro.serve import Engine

    cfg = configs.get_smoke(arch)
    params, qcfg = serve.load_quantized(cfg, jax.random.PRNGKey(0), "packed")
    prompts = [np.asarray(jax.random.randint(
        jax.random.fold_in(jax.random.PRNGKey(3), i), (l,), 4,
        cfg.vocab_size)) for i, l in enumerate((4, 7, 11))]

    def run(fused_kernels):
        eng = Engine(cfg, params, qcfg, n_slots=3, block_size=8,
                     n_blocks=12, max_blocks_per_slot=4,
                     fused_kernels=fused_kernels)
        rids = [eng.submit(p, 5) for p in prompts]
        outs = eng.drain(max_steps=500)
        return eng, [outs[r] for r in rids]

    eng_on, toks_on = run("on")
    assert eng_on.fused and eng_on.stats()["fused_kernels"]
    assert eng_on.sq.packed_backend == "grouped"
    eng_off, toks_off = run("off")
    assert not eng_off.fused
    for a, b in zip(toks_on, toks_off):
        assert np.array_equal(a, b)


def test_engine_fused_kernels_validation():
    from repro import configs
    from repro.launch import serve
    from repro.serve import Engine

    cfg = configs.get_smoke("qwen1.5-0.5b")
    params, qcfg = serve.load_quantized(cfg, jax.random.PRNGKey(0), "qdq")
    with pytest.raises(ValueError, match="fused_kernels"):
        Engine(cfg, params, qcfg, fused_kernels="maybe")


@pytest.mark.parametrize("blocks,fused", [(4, True), (2048, False)],
                         ids=["fits", "over_vmem"])
def test_engine_auto_falls_back_when_strip_exceeds_vmem(blocks, fused):
    """A page strip past the kernel's scoped VMEM (32k keys at
    qwen1.5-0.5b's heads) serves through the two-step under "auto", and
    "on" refuses it instead of failing to compile."""
    from repro import configs
    from repro.kernels import paged_attention
    from repro.launch import serve
    from repro.serve import Engine

    full = configs.get_config("qwen1.5-0.5b")
    strip = (full.n_kv_heads, full.head_dim, 1)
    assert paged_attention.fits_vmem(1056, *strip)
    assert not paged_attention.fits_vmem(32768, *strip)

    cfg = configs.get_smoke("qwen1.5-0.5b")
    params, qcfg = serve.load_quantized(cfg, jax.random.PRNGKey(0), "qdq")
    kw = dict(n_slots=1, block_size=16, n_blocks=8,
              max_blocks_per_slot=blocks)
    assert paged_attention.fits_vmem(
        blocks * 16, cfg.n_kv_heads, cfg.head_dim,
        cfg.n_heads // cfg.n_kv_heads) == fused
    assert Engine(cfg, params, qcfg, fused_kernels="auto", **kw).fused \
        == fused
    if not fused:
        with pytest.raises(ValueError, match="VMEM"):
            Engine(cfg, params, qcfg, fused_kernels="on", **kw)
