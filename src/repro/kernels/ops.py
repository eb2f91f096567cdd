"""Public jit'd wrappers for the Pallas kernels.

On this CPU container, kernels run in interpret mode (the kernel body is
executed in Python for correctness validation); on TPU, ``interpret=False``
lowers through Mosaic with the lane-aligned scale layout
(``nvfp4_matmul.swizzle_scales``).  ``interpret_default()`` auto-detects —
lazily, so importing this module never initializes the jax backend (the
multi-pod dry-run must set its forced device count before first backend
use) — and honors ``REPRO_PALLAS_INTERPRET=0/1`` as an explicit override
(benches/CI A/B the lowering path without code edits).  The probe result
is cached; tests that flip the env var call
``interpret_default.cache_clear()``.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.core.nvfp4 import PackedNVFP4, pack, unpack_layout
from repro.obs import dispatch as obs_dispatch

from . import ref
from .kl_loss import kl_loss as _kl_loss
from .nvfp4_matmul import nvfp4_matmul as _nvfp4_matmul
from .nvfp4_matmul import nvfp4_matmul_grouped as _nvfp4_matmul_grouped
from .nvfp4_matmul import nvfp4_matmul_tp as _nvfp4_matmul_tp
from .nvfp4_qdq import nvfp4_qdq as _nvfp4_qdq
from .paged_attention import paged_attention as _paged_attention


@functools.cache
def interpret_default() -> bool:
    env = os.environ.get("REPRO_PALLAS_INTERPRET", "").strip()
    if env in ("0", "1"):
        return env == "1"          # explicit override wins over auto-detect
    if env:
        raise ValueError(
            f"REPRO_PALLAS_INTERPRET={env!r}: expected '0' or '1'")
    return jax.default_backend() != "tpu"


def _note(name: str) -> None:
    """Count one kernel-wrapper dispatch if an engine step is recording.

    These wrappers execute Python only while jax traces a specialization,
    so the count is per-compile, not per-step — see ``repro.obs.dispatch``.
    """
    rec = obs_dispatch.active()
    if rec is not None:
        rec.kernel(name)


def nvfp4_qdq(x: jax.Array, tensor_amax=None, **kw) -> jax.Array:
    """Fused NVFP4 fake-quant (blocked along last dim)."""
    kw.setdefault("interpret", interpret_default())
    _note("nvfp4_qdq")
    with jax.named_scope("repro.nvfp4_qdq"):
        return _nvfp4_qdq(x, tensor_amax, **kw)


def pack_weight(w: jax.Array) -> PackedNVFP4:
    """Pack a [K, N] weight into the kernel's W^T:[N, K] NVFP4 layout."""
    return pack(w.T)


def nvfp4_matmul(x: jax.Array, packed: PackedNVFP4, **kw) -> jax.Array:
    """y = x @ W from packed NVFP4 weights, dequantized on the fly in VMEM."""
    kw.setdefault("interpret", interpret_default())
    _note("nvfp4_matmul")
    with jax.named_scope("repro.nvfp4_matmul"):
        return _nvfp4_matmul(x, packed, **kw)


def nvfp4_matmul_grouped(x: jax.Array, packed: PackedNVFP4,
                         **kw) -> jax.Array:
    """y[g] = x[g] @ W_g for a packed stack [G, N, K] in one grouped launch
    (the fused MoE decode GEMM — no per-expert dequant to HBM)."""
    kw.setdefault("interpret", interpret_default())
    _note("nvfp4_matmul_grouped")
    with jax.named_scope("repro.nvfp4_matmul_grouped"):
        return _nvfp4_matmul_grouped(x, packed, **kw)


def nvfp4_matmul_tp(x: jax.Array, packed: PackedNVFP4, mesh,
                    parallelism: str, **kw) -> jax.Array:
    """Tensor-parallel ``x @ W``: shard_map'd kernel over per-shard packed
    tiles — "column" shards N (no collective), "row" shards K (psum)."""
    kw.setdefault("interpret", interpret_default())
    _note("nvfp4_matmul_tp")
    with jax.named_scope("repro.nvfp4_matmul_tp"):
        return _nvfp4_matmul_tp(x, packed, mesh, parallelism, **kw)


def paged_attention(q: jax.Array, pool: dict, layer: jax.Array,
                    block_tables: jax.Array, pos: jax.Array, *,
                    window: int = 0, **kw) -> jax.Array:
    """Fused page-gather + FP8-dequant + attend over layer ``layer`` of the
    whole paged pool (read in place: no layer slice is materialized).

    Drop-in for the ``paged_gather_layer`` -> ``paged_attend`` two-step
    (``models.attention``), which remains its parity oracle — bitwise for
    BF16 pools, per-element FP8 dequant identical for FP8 pools.
    """
    kw.setdefault("interpret", interpret_default())
    _note("paged_attention")
    with jax.named_scope("repro.paged_attention"):
        return _paged_attention(q, pool["k"], pool["v"], block_tables, pos,
                                layer, pool.get("k_scale"),
                                pool.get("v_scale"), window=window, **kw)


def dequant_weight(packed: PackedNVFP4, contract_axis: int,
                   dtype=jnp.bfloat16) -> jax.Array:
    """Dequantize a packed weight back to its original dense layout.

    The non-kernel half of the packed-GEMM dispatch: >2-D (MoE expert)
    weights and ``packed_backend="dequant"`` configs take this path, then a
    plain einsum — which XLA/GSPMD can shard freely.
    """
    return unpack_layout(packed, contract_axis, dtype)


def kl_loss(t_logits: jax.Array, s_logits: jax.Array, mask: jax.Array,
            tile_t: int = 256, tile_v: int = 2048,
            interpret: bool | None = None) -> jax.Array:
    """Streaming masked-mean KL(p_t || p_s) over [T, V] logits."""
    if interpret is None:
        interpret = interpret_default()
    _note("kl_loss")
    with jax.named_scope("repro.kl_loss"):
        return _kl_loss(t_logits, s_logits, mask, tile_t, tile_v, interpret)


__all__ = ["nvfp4_qdq", "nvfp4_matmul", "nvfp4_matmul_grouped",
           "nvfp4_matmul_tp", "paged_attention", "pack_weight",
           "dequant_weight", "kl_loss", "ref", "interpret_default"]
