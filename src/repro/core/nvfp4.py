"""NVFP4 quantization algebra.

NVFP4 (NVIDIA, 2025) is a 4-bit floating-point format:

  * values:  FP4 E2M1  — magnitudes {0, .5, 1, 1.5, 2, 3, 4, 6}
  * block:   16 contiguous elements along the contraction (last) dim
  * scales:  two-level — per-block FP8 E4M3 scale  ×  per-tensor FP32 scale

Quantization of a tensor ``x`` (last dim = contraction dim):

  s_tensor = amax(|x|) / (448 * 6)                      # FP32, per tensor
  s_block  = cast_e4m3( amax_block(|x|) / 6 / s_tensor )  # FP8, per 16 elems
  q        = cast_e2m1( x / (s_block * s_tensor) )
  dq       = q * s_block * s_tensor

This module is the *reference* (pure-jnp) implementation; the Pallas kernel in
``repro.kernels.nvfp4_qdq`` is tiled for TPU VMEM and validated against this.

Everything here is shape-polymorphic over leading dims; the block axis is
always the LAST axis and must be divisible by ``BLOCK`` (callers pad).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

BLOCK = 16                      # NVFP4 block size
E2M1_MAX = 6.0                  # max magnitude representable in E2M1
E4M3_MAX = 448.0                # max magnitude representable in E4M3 (fn)
FP8_E4M3 = jnp.float8_e4m3fn
FP4_E2M1 = jnp.float4_e2m1fn

# Weight-memory footprint of one NVFP4 element, in bytes:
#   4 bits code + 8 bits E4M3 scale / 16 elems  (+ amortized fp32 tensor scale)
BYTES_PER_ELEM = 0.5 + 1.0 / BLOCK


def e2m1_round(a: jax.Array) -> jax.Array:
    """Round |values| (assumed in [0, 6]) to the E2M1 grid, RNE.

    The E2M1 magnitude grid is {0,.5,1,1.5,2,3,4,6}: spacing 0.5 below 2.0,
    1.0 in (2,4], 2.0 in (4,6].  ``jnp.round`` is round-half-to-even, which
    matches the hardware RNE semantics exactly (validated against ml_dtypes'
    float4_e2m1fn cast in tests).
    """
    return jnp.where(
        a <= 2.0,
        jnp.round(a * 2.0) * 0.5,
        jnp.where(a <= 4.0, jnp.round(a), jnp.round(a * 0.5) * 2.0),
    )


def e2m1_quantize(y: jax.Array) -> jax.Array:
    """Quantize scaled values y (|y| <= 6 after clipping) to the E2M1 grid."""
    a = jnp.clip(jnp.abs(y), 0.0, E2M1_MAX)
    return jnp.sign(y) * e2m1_round(a)


def e4m3_quantize(s: jax.Array) -> jax.Array:
    """Round positive scales to E4M3 (fn), clamping to the representable range.

    E4M3fn has no inf; overflow saturates at 448.  Zero/subnormal scales are
    floored to the smallest normal to keep division well-behaved.
    """
    s = jnp.clip(s, 2.0 ** -6, E4M3_MAX)
    return s.astype(FP8_E4M3).astype(jnp.float32)


class NVFP4Scales(NamedTuple):
    """The two-level scale pair for a blocked tensor."""
    block: jax.Array    # f32 (stored values are exactly-E4M3), shape x.shape[:-1] + (x.shape[-1]//16,)
    tensor: jax.Array   # f32 scalar


def compute_scales(x: jax.Array, tensor_amax: jax.Array | None = None) -> NVFP4Scales:
    """Compute NVFP4 two-level scales for ``x`` (blocked along last axis).

    ``tensor_amax`` may be supplied from calibration (PTQ static activation
    scaling); otherwise it is taken from ``x`` itself (dynamic quantization).
    """
    xf = x.astype(jnp.float32)
    *lead, k = xf.shape
    xb = jnp.abs(xf).reshape(*lead, k // BLOCK, BLOCK)
    block_amax = jnp.max(xb, axis=-1)
    if tensor_amax is None:
        tensor_amax = jnp.max(block_amax)
    s_tensor = jnp.maximum(tensor_amax.astype(jnp.float32), 1e-30) / (E4M3_MAX * E2M1_MAX)
    s_block = e4m3_quantize(block_amax / E2M1_MAX / s_tensor)
    return NVFP4Scales(block=s_block, tensor=s_tensor)


def quantize_blocked(x: jax.Array, scales: NVFP4Scales) -> jax.Array:
    """E2M1-quantize ``x`` given scales; returns f32 values on the E2M1 grid."""
    xf = x.astype(jnp.float32)
    *lead, k = xf.shape
    xb = xf.reshape(*lead, k // BLOCK, BLOCK)
    s = (scales.block * scales.tensor)[..., None]
    y = xb / jnp.maximum(s, 1e-30)
    return e2m1_quantize(y)


def qdq(x: jax.Array, tensor_amax: jax.Array | None = None) -> jax.Array:
    """Fake-quantize: quantize to NVFP4 then dequantize back to x.dtype.

    This is the numerics of an NVFP4 GEMM input as seen by the MXU: the
    QAD/QAT student forward pass applies this to weights and activations.
    """
    scales = compute_scales(x, tensor_amax)
    q = quantize_blocked(x, scales)
    s = (scales.block * scales.tensor)[..., None]
    *lead, k = x.shape
    return (q * s).reshape(*lead, k).astype(x.dtype)


@jax.custom_vjp
def fake_quant(x: jax.Array) -> jax.Array:
    """QDQ with a straight-through estimator (gradients pass through).

    Used on every quantized GEMM input during QAD/QAT training.  The paper
    keeps gradients in high precision (only Fprop is quantized, Fig. 2);
    the STE is the standard choice for the non-differentiable rounding.
    """
    return qdq(x)


def _fq_fwd(x):
    return qdq(x), None


def _fq_bwd(_, g):
    return (g,)


fake_quant.defvjp(_fq_fwd, _fq_bwd)


@jax.custom_vjp
def fake_quant_calibrated(x: jax.Array, tensor_amax: jax.Array) -> jax.Array:
    """STE QDQ with a calibration-provided per-tensor amax (PTQ activations)."""
    return qdq(x, tensor_amax)


def _fqc_fwd(x, tensor_amax):
    return qdq(x, tensor_amax), None


def _fqc_bwd(_, g):
    return (g, jnp.zeros((), g.dtype))


fake_quant_calibrated.defvjp(_fqc_fwd, _fqc_bwd)


# ---------------------------------------------------------------------------
# Packed representation — the deployment format (0.5625 B/param on TPU).
# ---------------------------------------------------------------------------

# E2M1 nibble decode table, computed arithmetically (no gather needed):
#   nibble n: sign = n>>3, exp = (n>>1)&3, man = n&1
#   exp==0 -> val = man * 0.5 (subnormal); exp>0 -> val = (1 + man/2) * 2^(exp-1)


def _nibble_to_f32(n: jax.Array) -> jax.Array:
    sign = 1.0 - 2.0 * (n >> 3).astype(jnp.float32)
    exp = ((n >> 1) & 3).astype(jnp.float32)
    man = (n & 1).astype(jnp.float32)
    mag = jnp.where(exp == 0, man * 0.5, (1.0 + 0.5 * man) * jnp.exp2(exp - 1.0))
    return sign * mag


def _f32_to_nibble(q: jax.Array) -> jax.Array:
    """Inverse of _nibble_to_f32 for values already ON the E2M1 grid."""
    sign = (q < 0).astype(jnp.uint8) << 3
    a = jnp.abs(q)
    # magnitudes {0,.5,1,1.5,2,3,4,6} -> codes {0,1,2,3,4,5,6,7} via 2*a ramp:
    # 0->0, .5->1, 1->2, 1.5->3, 2->4, 3->5, 4->6, 6->7
    code = jnp.where(a <= 2.0, jnp.round(a * 2.0),
                     jnp.where(a <= 4.0, jnp.round(a) + 2.0, 7.0)).astype(jnp.uint8)
    return sign | code


@dataclasses.dataclass(frozen=True)
class PackedNVFP4:
    """A tensor stored in true NVFP4 memory layout — the deployment QTensor.

    The packed (contraction) axis is always LAST; callers that quantize a
    weight along ``contract_axis`` first move that axis to the end, so the
    stored layout is W^T-style: codes[..., N, K//2].

    ``codes``  uint8 [..., K//2]   — two E2M1 nibbles per byte (even idx = low)
    ``scales`` float8_e4m3fn [..., K//16] — per-block scales
    ``tensor_scale`` f32 — scalar, or shape [*lead, 1, ..., 1] when the
        leading (layer-stack) axes carry independent per-slice scales (so the
        pytree slices cleanly through ``jax.lax.scan`` over layers)
    ``orig_k``  static: the un-padded logical K (0 → codes K*2, no padding)

    Registered as a pytree node: codes/scales/tensor_scale are leaves (they
    flow through jit / scan / checkpointing), ``orig_k`` is static metadata.
    """
    codes: jax.Array
    scales: jax.Array
    tensor_scale: jax.Array
    orig_k: int = 0

    @property
    def k(self) -> int:
        return self.orig_k or self.codes.shape[-1] * 2

    @property
    def shape(self):
        *lead, _ = self.codes.shape
        return (*lead, self.k)

    @property
    def ndim(self) -> int:
        return self.codes.ndim

    @property
    def nbytes(self) -> int:
        return (self.codes.nbytes + self.scales.nbytes
                + self.tensor_scale.size * 4)

    def nbytes_per_elem(self) -> float:
        return BYTES_PER_ELEM


jax.tree_util.register_dataclass(
    PackedNVFP4,
    data_fields=["codes", "scales", "tensor_scale"],
    meta_fields=["orig_k"])


def tp_shard_mode(p: PackedNVFP4, n_shards: int,
                  parallelism: str | None) -> str | None:
    """Which tensor-parallel layout a 2-D packed weight admits at
    ``n_shards`` — the single eligibility rule shared by the ``shard_map``
    GEMM dispatch (``layers.qeinsum``) and the device-placement resolver
    (``distributed.sharding.resolve_packed``), so the kernel's per-shard
    tiles always agree with where GSPMD actually put the bytes.

    ``"column"`` — codes/scales rows (the output dim N) split ``n_shards``
    ways; every shard runs the kernel with the full K, so each output
    element is computed exactly as on a single device (bitwise).
    ``"row"`` — the packed K dim splits; requires whole 16-element blocks
    per shard and no K padding, and the per-shard partial products are
    psum'd (fp32 adds reassociate by one reduction step).
    ``None`` — not shardable this way; callers fall back to the
    GSPMD-shardable dequant-einsum path.
    """
    if n_shards <= 1 or p.ndim != 2 or parallelism not in ("column", "row"):
        return None
    n, kh = p.codes.shape
    if parallelism == "column":
        return "column" if n % n_shards == 0 else None
    kp = kh * 2
    ok = (p.k == kp and kh % n_shards == 0
          and (kp // BLOCK) % n_shards == 0)
    return "row" if ok else None


def pack(x: jax.Array, n_lead: int = 0) -> PackedNVFP4:
    """Quantize ``x`` to the packed NVFP4 deployment layout.

    ``n_lead``: number of leading axes (layer-stack dims) that each get an
    independent per-tensor scale — required so a stacked [L, ...] weight
    sliced per-layer by ``jax.lax.scan`` carries the right scalar scale.
    """
    tensor_amax = None
    if n_lead:
        tensor_amax = jnp.max(jnp.abs(x.astype(jnp.float32)),
                              axis=tuple(range(n_lead, x.ndim)), keepdims=True)
    scales = compute_scales(x, tensor_amax)
    q = quantize_blocked(x, scales)          # [..., K//16, 16] on grid
    *lead, k = x.shape
    nib = _f32_to_nibble(q).reshape(*lead, k)
    lo, hi = nib[..., 0::2], nib[..., 1::2]
    return PackedNVFP4(
        codes=(lo | (hi << 4)).astype(jnp.uint8),
        scales=scales.block.astype(FP8_E4M3),
        tensor_scale=scales.tensor,
        orig_k=k,
    )


def unpack(p: PackedNVFP4, dtype=jnp.bfloat16) -> jax.Array:
    """Dequantize a packed tensor back to ``dtype`` (reference path).

    The Pallas kernel ``repro.kernels.nvfp4_matmul`` performs this dequant
    on-the-fly in VMEM fused with the GEMM; this function is its oracle and
    the GSPMD-shardable fallback used by the distributed serve path.
    Returns the full (padded) K; see ``unpack_layout`` for the logical view.
    """
    codes = p.codes
    lo = _nibble_to_f32(codes & jnp.uint8(0xF))
    hi = _nibble_to_f32(codes >> 4)
    *lead, kh = codes.shape
    vals = jnp.stack([lo, hi], axis=-1).reshape(*lead, kh * 2)
    vb = vals.reshape(*lead, kh * 2 // BLOCK, BLOCK)
    s = (p.scales.astype(jnp.float32) * p.tensor_scale)[..., None]
    return (vb * s).reshape(*lead, kh * 2).astype(dtype)


def unpack_layout(p: PackedNVFP4, contract_axis: int,
                  dtype=jnp.bfloat16) -> jax.Array:
    """Dequantize to the ORIGINAL weight layout.

    Inverse of ``moveaxis(w, contract_axis, -1); pad; pack``: strips K
    padding and moves the packed axis back to ``contract_axis``.  This is the
    dequant-then-einsum fallback used for >2-D (MoE expert) weights and
    non-kernel backends.
    """
    w = unpack(p, dtype)
    if p.orig_k and p.orig_k != w.shape[-1]:
        w = w[..., : p.orig_k]
    return jnp.moveaxis(w, -1, contract_axis % w.ndim)


# ---------------------------------------------------------------------------
# FP8 KV-cache quantization (paper §3.4: Nemotron 3 Nano quantizes KV to FP8).
# ---------------------------------------------------------------------------


class FP8Tensor(NamedTuple):
    values: jax.Array   # float8_e4m3fn
    scale: jax.Array    # f32, broadcastable to values


def fp8_quantize(x: jax.Array, axis: int | tuple = -1) -> FP8Tensor:
    """Per-slice (default: per last axis position removed) symmetric FP8 quant."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / E4M3_MAX
    return FP8Tensor(values=(xf / scale).astype(FP8_E4M3), scale=scale)


def fp8_dequantize(t: FP8Tensor, dtype=jnp.bfloat16) -> jax.Array:
    return (t.values.astype(jnp.float32) * t.scale).astype(dtype)
