"""FP8 KV-cache numerics: quant/dequant roundtrip error bounds, prefill /
decode parity between BF16 and FP8 caches, and dense-vs-paged write
equivalence (the engine's FP8 pages must store exactly what the static
cache stores).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.core.qconfig import QuantConfig
from repro.models import attention as attn
from repro.models import decoder


def test_quant_dequant_roundtrip_bounds():
    """E4M3 per-(pos, head) quantization: relative error bounded by the
    format's half-ulp (2^-4) against each vector's amax, zeros exact."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 4, 32),
                          jnp.float32) * 3.0
    x = x.at[0, 0, 0].set(0.0)                     # an all-zero vector
    vals, scale = attn._quant_kv(x)
    assert vals.dtype == jnp.float8_e4m3fn
    assert scale.shape == x.shape[:-1]             # one scale per (pos, head)
    dq = np.asarray(attn._dequant_kv(vals, scale, jnp.float32))

    amax = np.max(np.abs(np.asarray(x)), axis=-1, keepdims=True)
    err = np.abs(dq - np.asarray(x))
    assert np.all(err <= amax * 2.0 ** -4 + 1e-12)
    np.testing.assert_array_equal(dq[0, 0, 0], np.zeros(32))
    # scales are positive even for the zero vector (division stays finite)
    assert np.all(np.asarray(scale) > 0)


def test_roundtrip_idempotent():
    """Re-quantizing already-quantized values is exact (values on-grid)."""
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 5, 2, 16), jnp.float32)
    dq1 = attn._dequant_kv(*attn._quant_kv(x), jnp.float32)
    dq2 = attn._dequant_kv(*attn._quant_kv(dq1), jnp.float32)
    np.testing.assert_array_equal(np.asarray(dq1), np.asarray(dq2))


def _cfg_pair():
    cfg = configs.get_smoke("qwen1.5-0.5b")
    return cfg, dataclasses.replace(cfg, quant_recipe="moe_hybrid")


def test_prefill_logits_identical_bf16_vs_fp8_cache():
    """FP8 only affects the cache: prefill attention runs on BF16 KV before
    quantization, so prefill logits are bitwise equal across cache dtypes."""
    cfg_bf16, cfg_fp8 = _cfg_pair()
    params = decoder.init_params(cfg_bf16, jax.random.PRNGKey(2))
    qcfg = QuantConfig(quantize_weights=False)     # same policy both runs
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 12), 4,
                              cfg_bf16.vocab_size)
    l16, c16 = decoder.prefill(cfg_bf16, params, {"tokens": toks}, qcfg,
                               s_max=16)
    l8, c8 = decoder.prefill(cfg_fp8, params, {"tokens": toks}, qcfg,
                             s_max=16)
    np.testing.assert_array_equal(np.asarray(l16, np.float32),
                                  np.asarray(l8, np.float32))
    assert c16["k"].dtype == jnp.bfloat16
    assert c8["k"].dtype == jnp.float8_e4m3fn and "k_scale" in c8


def test_decode_parity_bf16_vs_fp8_cache():
    """Greedy decode from the two caches stays close at smoke scale: FP8
    perturbs logits within the roundtrip bound, not catastrophically."""
    cfg_bf16, cfg_fp8 = _cfg_pair()
    params = decoder.init_params(cfg_bf16, jax.random.PRNGKey(2))
    qcfg = QuantConfig(quantize_weights=False)
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 8), 4,
                              cfg_bf16.vocab_size)
    l16, c16 = decoder.prefill(cfg_bf16, params, {"tokens": toks}, qcfg,
                               s_max=12)
    l8, c8 = decoder.prefill(cfg_fp8, params, {"tokens": toks}, qcfg,
                             s_max=12)
    nxt = jnp.argmax(l16[:, -1:], -1).astype(jnp.int32)
    for _ in range(3):
        l16, c16 = decoder.decode_step(cfg_bf16, params, c16,
                                       {"tokens": nxt}, qcfg)
        l8, c8 = decoder.decode_step(cfg_fp8, params, c8,
                                     {"tokens": nxt}, qcfg)
        a, b = np.asarray(l16, np.float32), np.asarray(l8, np.float32)
        rms = np.sqrt(np.mean(a * a)) + 1e-9
        rms_diff = np.sqrt(np.mean((a - b) ** 2))
        corr = np.corrcoef(a.ravel(), b.ravel())[0, 1]
        # randomly initialized smoke model: FP8 KV perturbs, must not destroy
        assert rms_diff / rms < 0.5, f"FP8 KV drifted too far ({rms_diff/rms:.3f})"
        assert corr > 0.9, f"FP8 KV decorrelates logits ({corr:.3f})"
        nxt = jnp.argmax(l16[:, -1:], -1).astype(jnp.int32)


def test_paged_fp8_write_matches_dense_cache_write():
    """The paged pool stores bit-identical FP8 pages + scales to the dense
    ring cache for the same incoming KV."""
    rng = jax.random.PRNGKey(5)
    b, s_max, h, hd, bs = 3, 8, 2, 16, 4
    k_new = jax.random.normal(rng, (b, 1, h, hd), jnp.bfloat16)
    v_new = jax.random.normal(jax.random.fold_in(rng, 1), (b, 1, h, hd),
                              jnp.bfloat16)

    dense = {"k": jnp.zeros((b, s_max, h, hd), jnp.float8_e4m3fn),
             "v": jnp.zeros((b, s_max, h, hd), jnp.float8_e4m3fn),
             "k_scale": jnp.zeros((b, s_max, h), jnp.float32),
             "v_scale": jnp.zeros((b, s_max, h), jnp.float32)}
    pos = 5
    dense_out = attn.cache_update_layer(dense, k_new, v_new, pos)

    n_blocks, n_layers, layer = 6, 3, 1
    pool = {"k": jnp.zeros((n_layers, n_blocks, bs, h * hd),
                           jnp.float8_e4m3fn),
            "v": jnp.zeros((n_layers, n_blocks, bs, h * hd),
                           jnp.float8_e4m3fn),
            "k_scale": jnp.zeros((n_layers, n_blocks, bs, h), jnp.float32),
            "v_scale": jnp.zeros((n_layers, n_blocks, bs, h), jnp.float32)}
    # rows 0/2 active with distinct block tables; row 1 inactive
    tables = jnp.asarray([[0, 1], [2, 3], [4, 5]], jnp.int32)
    lens = jnp.full((b,), pos, jnp.int32)
    active = jnp.asarray([True, False, True])
    pool_out = attn.paged_update_layer(pool, layer, k_new, v_new, tables,
                                       lens, active)

    blk, off = pos // bs, pos % bs
    for row in (0, 2):
        pb = int(tables[row, blk])
        np.testing.assert_array_equal(
            np.asarray(pool_out["k"][layer, pb, off], np.float32),
            np.asarray(dense_out["k"][row, pos], np.float32).reshape(-1))
        np.testing.assert_array_equal(
            np.asarray(pool_out["k_scale"][layer, pb, off]),
            np.asarray(dense_out["k_scale"][row, pos]))
    # the inactive row (tables [2, 3]) wrote nothing anywhere, and no other
    # layer was touched
    np.testing.assert_array_equal(
        np.asarray(pool_out["k"][layer, 2:4], np.float32),
        np.zeros((2, bs, h * hd)))
    np.testing.assert_array_equal(np.asarray(pool_out["k_scale"][layer, 2:4]),
                                  np.zeros((2, bs, h)))
    for other in (0, 2):
        np.testing.assert_array_equal(
            np.asarray(pool_out["k"][other], np.float32),
            np.zeros((n_blocks, bs, h * hd)))
