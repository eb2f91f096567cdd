"""The paged forwards carry the whole KV pool through the layer scan and
write it in place at each layer's index.

Each forward is checked against a small xs/ys reference kept here: the
pool scanned layer by layer as the scan's ``xs``, each layer's new pages
collected as its ``ys`` — the form the forwards had before the pool moved
into the carry.  BF16 pools must match bit for bit, logits and pages; FP8
pools page for page and scale for scale (so their dequantized values are
identical).  The skip segments (``skip_first_layers``/``skip_last_layers``)
run as separate scans, so the carried layer index has to count across
them.  Last, the compiled, donated decode step must hold no copy of the
pool or of one of its layers, and almost no temporaries.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import qconfig
from repro.models import common, decoder, layers

ARCH = "qwen1.5-0.5b"
N_LAYERS = 6                 # room for a quantized middle between 2 + 2
N_SLOTS, MB, BS = 3, 3, 8
N_BLOCKS = N_SLOTS * MB + 3


def _cfg(fp8: bool):
    cfg = dataclasses.replace(configs.get_smoke(ARCH), n_layers=N_LAYERS)
    # the moe_hybrid recipe is what gives a decoder an FP8 pool
    return dataclasses.replace(cfg, quant_recipe="moe_hybrid") if fp8 else cfg


def _qcfg(skip: int):
    return dataclasses.replace(qconfig.NVFP4_ALL, skip_first_layers=skip,
                               skip_last_layers=skip, act_scope="token")


def _random(specs, key):
    """A state tree (pool or scratch) whose every page, layer and scale
    holds distinct values."""
    out = {}
    for i, (name, spec) in enumerate(sorted(specs.items())):
        x = jax.random.normal(jax.random.fold_in(key, i), spec.shape)
        if name.endswith("_scale"):
            x = jnp.abs(x) + 0.1
        out[name] = x.astype(spec.dtype)
    return out


def _pool(cfg, key):
    return _random(decoder.paged_pool_specs(cfg, N_BLOCKS, BS), key)


def _bits(x):
    x = np.asarray(x)
    return x.view(np.uint8) if x.dtype.itemsize == 1 else x.astype(np.float32)


def _same(got, want):
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_array_equal(_bits(g), _bits(w))


def _inputs(cfg, key, k1: int = 1):
    bt = jax.random.permutation(key, N_BLOCKS)[: N_SLOTS * MB].reshape(
        N_SLOTS, MB).astype(jnp.int32)
    lens = jnp.asarray([5, 9, MB * BS - k1], jnp.int32)
    active = jnp.asarray([True, False, True])
    toks = jax.random.randint(jax.random.fold_in(key, 1), (N_SLOTS, k1), 4,
                              cfg.vocab_size)
    return bt, lens, active, toks


# ---------------------------------------------------------------------------
# the xs/ys reference
# ---------------------------------------------------------------------------


def _one_layer(pool_sl):
    return {k: v[None] for k, v in pool_sl.items()}


def _ref_paged(cfg, params, pool, bt, pos, positions, act, toks, qcfg,
               fused):
    """decode/verify with the pool scanned as xs and collected as ys."""
    x = decoder._embed_inputs(cfg, params, {"tokens": toks})

    def body(qc):
        def fn(x, inp):
            p, psl = inp
            h = decoder.run_norm(cfg, p["ln1"], x)
            a, new = decoder._attention_paged(
                qc, cfg, p, h, pos, _one_layer(psl), 0, bt, positions, act,
                fused=fused)
            y = x + a
            f, _ = decoder._ffn(qc, cfg, p, decoder.run_norm(cfg, p["ln2"], y))
            return y + f, {k: v[0] for k, v in new.items()}
        return fn

    x, new_pool = common.scan_layers(
        body, x, params["layers"], pool, qcfg, qcfg.skip_first_layers,
        qcfg.skip_last_layers, "none")
    x = decoder.run_norm(cfg, params["final_norm"], x)
    logits = layers.qdense(qcfg, "lm_head", x, decoder.unembed(cfg, params),
                           parallelism="column")
    return logits, new_pool


def _ref_prefill_chunk(cfg, params, scratch, pool, block_table, start,
                       n_valid, toks, qcfg):
    """chunked prefill with scratch and pool scanned as xs, ys."""
    x = decoder._embed_inputs(cfg, params, {"tokens": toks})
    c = x.shape[1]
    pos = (jnp.arange(c) + start)[None, :]
    positions = start + jnp.arange(c)
    tok_active = jnp.arange(c) < n_valid
    bt = jnp.broadcast_to(block_table[None, :], (c, block_table.shape[0]))

    def body(qc):
        def fn(x, inp):
            p, (ssl, psl) = inp
            h = decoder.run_norm(cfg, p["ln1"], x)
            a, s1, p1 = decoder._attention_prefill_chunk(
                qc, cfg, p, h, pos, _one_layer(ssl), _one_layer(psl), 0, bt,
                positions, tok_active, start, n_valid)
            y = x + a
            f, _ = decoder._ffn(qc, cfg, p, decoder.run_norm(cfg, p["ln2"], y))
            return y + f, ({k: v[0] for k, v in s1.items()},
                           {k: v[0] for k, v in p1.items()})
        return fn

    x, (new_scratch, new_pool) = common.scan_layers(
        body, x, params["layers"], (scratch, pool), qcfg,
        qcfg.skip_first_layers, qcfg.skip_last_layers, "none")
    x = decoder.run_norm(cfg, params["final_norm"], x)
    x_last = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)
    logits = layers.qdense(qcfg, "lm_head", x_last,
                           decoder.unembed(cfg, params), parallelism="column")
    return logits, new_scratch, new_pool


# ---------------------------------------------------------------------------
# carried pool == xs/ys reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("skip", [0, 2], ids=["noskip", "skip2+2"])
@pytest.mark.parametrize("fp8", [False, True], ids=["bf16", "fp8"])
@pytest.mark.parametrize("fused", [False, True], ids=["unfused", "fused"])
def test_decode_step_paged_carry_matches_xs_ys(fused, fp8, skip):
    cfg, qcfg = _cfg(fp8), _qcfg(skip)
    key = jax.random.PRNGKey(10 + 2 * skip + fp8)
    params = decoder.init_params(cfg, key)
    pool = _pool(cfg, jax.random.fold_in(key, 1))
    bt, lens, active, toks = _inputs(cfg, jax.random.fold_in(key, 2))
    got = jax.jit(lambda pool: decoder.decode_step_paged(
        cfg, params, pool, bt, lens, active, {"tokens": toks}, qcfg,
        fused=fused))(pool)
    want = jax.jit(lambda pool: _ref_paged(
        cfg, params, pool, bt, lens[:, None], lens, active, toks, qcfg,
        fused))(pool)
    _same(got, want)
    # the step wrote: active slots' new rows differ from the pool it read
    assert not np.array_equal(_bits(got[1]["k"]), _bits(pool["k"]))


@pytest.mark.parametrize("fp8", [False, True], ids=["bf16", "fp8"])
@pytest.mark.parametrize("fused", [False, True], ids=["unfused", "fused"])
def test_verify_step_paged_carry_matches_xs_ys(fused, fp8):
    cfg, qcfg, k1 = _cfg(fp8), _qcfg(2), 4
    key = jax.random.PRNGKey(30 + fp8)
    params = decoder.init_params(cfg, key)
    pool = _pool(cfg, jax.random.fold_in(key, 1))
    bt, lens, active, toks = _inputs(cfg, jax.random.fold_in(key, 2), k1)
    n_prop = jnp.asarray([3, 2, 1], jnp.int32)
    got = jax.jit(lambda pool: decoder.verify_step_paged(
        cfg, params, pool, bt, lens, active, n_prop, {"tokens": toks}, qcfg,
        fused=fused))(pool)
    positions = lens[:, None] + jnp.arange(k1)[None, :]
    tok_active = active[:, None] & (jnp.arange(k1)[None, :]
                                    <= n_prop[:, None])
    want = jax.jit(lambda pool: _ref_paged(
        cfg, params, pool, bt, positions, positions, tok_active, toks, qcfg,
        fused))(pool)
    _same(got, want)


@pytest.mark.parametrize("fp8", [False, True], ids=["bf16", "fp8"])
def test_prefill_chunk_paged_carry_matches_xs_ys(fp8):
    cfg, qcfg, c = _cfg(fp8), _qcfg(2), 8
    key = jax.random.PRNGKey(50 + fp8)
    params = decoder.init_params(cfg, key)
    pool = _pool(cfg, jax.random.fold_in(key, 1))
    scratch = _random(decoder.prefill_scratch_specs(cfg, MB * BS),
                      jax.random.fold_in(key, 3))
    bt = jax.random.permutation(jax.random.fold_in(key, 2), N_BLOCKS)[:MB]
    bt = bt.astype(jnp.int32)
    start, n_valid = jnp.int32(5), jnp.int32(6)
    toks = jax.random.randint(jax.random.fold_in(key, 4), (1, c), 4,
                              cfg.vocab_size)
    got = jax.jit(lambda scratch, pool: decoder.prefill_chunk_paged(
        cfg, params, scratch, pool, bt, start, n_valid, {"tokens": toks},
        qcfg))(scratch, pool)
    want = jax.jit(lambda scratch, pool: _ref_prefill_chunk(
        cfg, params, scratch, pool, bt, start, n_valid, toks, qcfg))(
            scratch, pool)
    _same(got, want)


# ---------------------------------------------------------------------------
# the compiled decode step updates the donated pool in place
# ---------------------------------------------------------------------------


def test_decode_step_compiles_to_in_place_pool_update():
    """No copy or dynamic-update-slice of the pool's or one layer's shape,
    and temporaries under 5% of the pool's bytes.  The pool is float32:
    the CPU routes every bf16 scatter through f32, which a bf16 pool would
    show as whole-pool converts whatever the step does.  The two-step
    attention path: interpret-mode Pallas copies its operands on the CPU,
    so the fused kernel's step is compiled for a described TPU instead
    (``test_chip_compile.py``)."""
    cfg = dataclasses.replace(configs.get_smoke(ARCH), n_layers=4)
    qcfg = _qcfg(1)
    params = jax.eval_shape(lambda: decoder.init_params(
        cfg, jax.random.PRNGKey(0)))
    pool = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32),
        jax.eval_shape(lambda: decoder.init_paged_pool(cfg, 2048, BS)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    step = jax.jit(
        lambda params, pool, bt, lens, active, toks:
        decoder.decode_step_paged(cfg, params, pool, bt, lens, active,
                                  {"tokens": toks}, qcfg),
        donate_argnums=(1,))
    compiled = step.lower(params, pool, i32(N_SLOTS, MB), i32(N_SLOTS),
                          jax.ShapeDtypeStruct((N_SLOTS,), jnp.bool_),
                          i32(N_SLOTS, 1)).compile()
    assert_pool_updated_in_place(compiled, pool)


def assert_pool_updated_in_place(compiled, pool):
    """The compiled step holds no copy or dynamic-update-slice of the pool
    or of one of its layers, and temporaries under 5% of the pool."""
    dims = set()
    for a in jax.tree.leaves(pool):
        for s in (a.shape, a.shape[1:], (1,) + a.shape[1:]):
            dims.add(",".join(map(str, s)))
    bad = [line.strip()[:160] for line in compiled.as_text().splitlines()
           if (m := re.search(r"= \w+\[([\d,]*)\]\S* (copy|dynamic-update-"
                              r"slice)\(", line)) and m.group(1) in dims]
    assert not bad, bad
    pool_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(pool))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 0.05 * pool_bytes, (temp, pool_bytes)
