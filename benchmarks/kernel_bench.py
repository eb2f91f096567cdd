"""Kernel microbenchmarks (interpret-mode on CPU: timings are indicative of
correctness paths, not TPU perf — the TPU story is in the roofline)."""
from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, "src")

from repro.core import nvfp4                    # noqa: E402
from repro.kernels import ops                   # noqa: E402

from .common import emit                        # noqa: E402


def _time(fn, *args, n=5):
    fn(*args)                                    # compile
    t0 = time.time()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / n * 1e6


def kernels():
    rng = jax.random.PRNGKey(0)
    x = jax.random.normal(rng, (512, 1024), jnp.bfloat16)

    us = _time(ops.nvfp4_qdq, x)
    emit("kernel/nvfp4_qdq_512x1024", us,
         f"bytes_per_elem_out={nvfp4.BYTES_PER_ELEM}")

    us = _time(jax.jit(nvfp4.qdq), x)
    emit("kernel/nvfp4_qdq_ref_512x1024", us, "oracle")

    w = jax.random.normal(rng, (1024, 512), jnp.float32)
    p = ops.pack_weight(w)
    us = _time(lambda a: ops.nvfp4_matmul(a, p), x.astype(jnp.float32))
    weight_bytes = p.codes.size + p.scales.size + 4
    emit("kernel/nvfp4_matmul_512x1024x512", us,
         f"weight_bytes={weight_bytes};bf16_bytes={w.size * 2};"
         f"traffic_ratio={w.size * 2 / weight_bytes:.2f}")

    t = jax.random.normal(rng, (256, 2048), jnp.float32)
    s = t + 0.1 * jax.random.normal(jax.random.fold_in(rng, 1), (256, 2048))
    mask = jnp.ones((256,))
    us = _time(lambda: ops.kl_loss(t, s, mask))
    emit("kernel/kl_loss_256x2048", us, "streaming_one_pass")

    from repro.kernels import ref
    us = _time(jax.jit(lambda: ref.kl_loss_ref(t, s, mask)))
    emit("kernel/kl_loss_ref_256x2048", us, "materializing_oracle")

    # --- fused serving-kernel tier ------------------------------------------
    from repro.models import attention as attn

    # fused one-pass paged attention vs the gather+dequant two-step.
    # decode geometry: 4 slots, 8 blocks x 16 tokens, GQA 8q/2kv, hd=64
    b, mb, bs, hkv, n_rep, hd = 4, 8, 16, 2, 4, 64
    kp = jax.random.normal(rng, (1, b * mb, bs, hkv * hd)
                           ).astype(jnp.bfloat16)
    vp = jax.random.normal(jax.random.fold_in(rng, 2),
                           (1, b * mb, bs, hkv * hd)).astype(jnp.bfloat16)
    pool = {"k": kp, "v": vp}               # one layer, the stored layout
    bt = jnp.arange(b * mb, dtype=jnp.int32).reshape(b, mb)
    pos = jnp.full((b,), mb * bs, jnp.int32)
    q = jax.random.normal(jax.random.fold_in(rng, 3),
                          (b, 1, hkv * n_rep, hd)).astype(jnp.bfloat16)
    # the dense [B, MB*bs, Hkv, hd] k+v intermediate the fused kernel never
    # materializes (written + re-read by the two-step, in HBM on TPU)
    gather_bytes = 2 * 2 * b * mb * bs * hkv * hd * 2
    case = f"{b}x{mb * bs}kv_h{hkv}x{n_rep}_hd{hd}"
    us = _time(jax.jit(
        lambda a: attn.paged_attend_fused(a, pool, 0, bt, pos)), q)
    emit(f"kernel/paged_attention_fused_{case}", us,
         f"one_pass;gather_intermediate_bytes_avoided={gather_bytes}")
    us = _time(jax.jit(lambda a: attn.paged_attend(a, pool, 0, bt, pos)), q)
    emit(f"kernel/paged_attention_gather_{case}", us,
         f"gather_dequant_baseline;intermediate_bytes={gather_bytes}")

    # grouped NVFP4 decode GEMM (one launch over the expert grid) vs the
    # dequant-to-HBM + einsum baseline.  MoE decode geometry: 8 experts,
    # 4 routed rows each.
    g, m, k, n = 8, 4, 512, 512
    xg = jax.random.normal(rng, (g, m, k), jnp.float32)
    wg = jax.random.normal(jax.random.fold_in(rng, 4), (g, n, k),
                           jnp.float32)
    pg = nvfp4.pack(wg, n_lead=1)
    packed_bytes = pg.codes.size + pg.scales.size + 4 * g
    dequant_bytes = g * k * n * 2                  # bf16 slab the baseline writes
    us = _time(jax.jit(lambda a: ops.nvfp4_matmul_grouped(a, pg)), xg)
    emit(f"kernel/nvfp4_matmul_grouped_{g}x{m}x{k}x{n}", us,
         f"weight_bytes={packed_bytes};dequant_slab_bytes_avoided="
         f"{dequant_bytes}")
    us = _time(jax.jit(lambda a: jnp.einsum(
        "gmk,gkn->gmn", a, ops.dequant_weight(pg, 1))), xg)
    emit(f"kernel/nvfp4_grouped_dequant_einsum_{g}x{m}x{k}x{n}", us,
         f"dequant_baseline;slab_bytes={dequant_bytes};"
         f"traffic_ratio={dequant_bytes / packed_bytes:.2f}")
