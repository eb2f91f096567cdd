"""End-to-end system behaviour: train driver, serve driver, generated data."""
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.qconfig import BF16
from repro.data import generated
from repro.launch.serve import load_quantized, serve_batch
from repro.launch.train import train
from repro.models import get_model


def test_train_driver_qad_improves_kl():
    _, hist = train(arch="qwen1.5-0.5b", smoke=True, steps=60, lr=1e-3,
                    method="qad", batch=4, seq=32, eval_every=30,
                    log=lambda *a: None)
    assert hist[-1]["kl"] < hist[0]["kl"]
    assert np.isfinite(hist[-1]["ce"])


def test_serve_driver_batched_decode():
    cfg = configs.get_smoke("qwen1.5-0.5b")
    rng = jax.random.PRNGKey(0)
    params, qcfg = load_quantized(cfg, rng)
    prompts = jax.random.randint(rng, (3, 8), 4, cfg.vocab_size)
    toks, stats = serve_batch(cfg, params, prompts, n_gen=6)
    assert toks.shape == (3, 6)
    assert stats["decode_tok_s"] > 0


def test_serve_greedy_decode_is_deterministic():
    cfg = configs.get_smoke("olmo-1b")
    rng = jax.random.PRNGKey(1)
    params, _ = load_quantized(cfg, rng)
    prompts = jax.random.randint(rng, (2, 8), 4, cfg.vocab_size)
    t1, _ = serve_batch(cfg, params, prompts, n_gen=5)
    t2, _ = serve_batch(cfg, params, prompts, n_gen=5)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))


def test_generated_data_pipeline():
    """Teacher-generated QAD data (paper §4.1): BOS-seeded sampling."""
    cfg = configs.get_smoke("olmo-1b")
    model = get_model(cfg)
    params = model.init_params(cfg, jax.random.PRNGKey(2))
    prompts = generated.bos_prompts(batch=2)
    toks = generated.generate_tokens(model, cfg, params, prompts, n_new=9,
                                     rng=jax.random.PRNGKey(3))
    assert toks.shape == (2, 10)
    batch = generated.batch_from_generated(toks, seq_len=9)
    assert batch["tokens"].shape == (2, 9)
    np.testing.assert_array_equal(np.asarray(batch["tokens"][:, 1:]),
                                  np.asarray(batch["labels"][:, :-1]))


def test_packed_weight_serving_matches_qdq():
    """weight_format='packed' stores true 4-bit codes; unpacking them must
    reproduce the QDQ'd weights the accuracy eval used."""
    from repro.core import nvfp4
    cfg = configs.get_smoke("qwen1.5-0.5b")
    rng = jax.random.PRNGKey(4)
    qdq_params, _ = load_quantized(cfg, rng, weight_format="qdq")
    packed_params, _ = load_quantized(cfg, rng, weight_format="packed")
    w_q = qdq_params["layers"]["wg"]
    w_p = packed_params["layers"]["wg"]
    assert isinstance(w_p, nvfp4.PackedNVFP4)
    # packed layout is blocked along the contraction axis (moved to last)
    up = nvfp4.unpack(w_p, jnp.float32)
    up = jnp.moveaxis(up, -1, 1)              # contract axis was 1 (stacked L)
    np.testing.assert_allclose(np.asarray(up),
                               np.asarray(w_q, np.float32), rtol=1e-2,
                               atol=1e-3)


@pytest.mark.parametrize("placed", [True, False], ids=["env", "checkout"])
def test_compile_cache_placement(monkeypatch, tmp_path, placed):
    """The entry points' compile cache: where JAX_COMPILATION_CACHE_DIR is
    set it is left to jax and nothing else is set; otherwise the cache goes
    to the fixed directory inside the checkout."""
    from repro.launch import compile_cache
    prev = jax.config.jax_compilation_cache_dir
    if placed:
        monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    else:
        monkeypatch.delenv(compile_cache.ENV, raising=False)
    try:
        got = compile_cache.enable_compile_cache()
        if placed:
            assert got == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == prev
        else:
            checkout = Path(__file__).resolve().parents[1]
            assert got == str(checkout / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
