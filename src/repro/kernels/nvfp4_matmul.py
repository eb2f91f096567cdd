"""Pallas TPU kernel: matmul with packed-NVFP4 weights, dequant-on-the-fly.

This is the TPU-native deployment path for NVFP4 inference (DESIGN.md §3):
Blackwell gets an FP4 *compute* win; TPU has no FP4 MXU, but decode is
memory-bound, so the win on TPU is streaming 0.5625 B/param instead of
2 B/param.  Weights live in HBM as packed nibbles + E4M3 block scales; each
(TN, TK) weight tile is unpacked and rescaled in VMEM/VREGs and fed to the
BF16 MXU with FP32 accumulation.

This kernel is wired into the live serving path: PTQ with
``weight_format="packed"`` leaves ``PackedNVFP4`` pytree nodes in the param
tree, and every 2-D quantized GEMM (``layers.qeinsum`` dispatch) lands here
— including M=1 decode steps, whose tiles are padded up to the fp32 sublane
minimum (8).  Dequantized weight tiles are rounded to BF16 before the dot so
the kernel is numerically interchangeable with serving the QDQ'd BF16
weights through XLA (that is what the MXU consumes either way).

Layout: for y = x @ W with x:[M,K], the weight is stored transposed,
W^T:[N,K], packed along K (the contraction dim — NVFP4 blocks must run along
K so a GEMM consumes whole blocks):

    codes  uint8          [N, K//2]    two E2M1 nibbles / byte
    scales float8_e4m3fn  [N, K//16]
    tensor_scale f32      [] (or any size-1 shape, e.g. a scan-sliced [1,1])

``packed.orig_k`` (the un-padded logical K) may be smaller than the stored
K; ``x`` is padded with zeros to match — the pad region of the codes is
zero, so it contributes nothing.

Byte j holds K element 2j in its low nibble and 2j+1 in its high one.  The
wrapper splits x into its even and odd columns, so the kernel contracts
each with one nibble plane as stored and never interleaves them.

Grid (n, m, k) with K innermost; an FP32 VMEM scratch tile accumulates
across K steps and is flushed to the output on the last step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.nvfp4 import BLOCK, PackedNVFP4


def _nibble_to_f32(n):
    sign = 1.0 - 2.0 * (n >> 3).astype(jnp.float32)
    exp = ((n >> 1) & 3).astype(jnp.float32)
    man = (n & 1).astype(jnp.float32)
    mag = jnp.where(exp == 0, man * 0.5, (1.0 + 0.5 * man) * jnp.exp2(exp - 1.0))
    return sign * mag


def _lane_map(a, width: int, hit):
    """``out[:, j] = a[:, i]`` where ``hit(i, j)`` — at most one i per
    column j, zero where none — as a [rows, width] matmul with a 0/1 matrix.

    Mosaic lowers no reshape that splits or merges the lane dim, so the
    block-scale repeat runs on the MXU.  Exact for bf16-representable ``a``
    (E4M3 scales): each output is one product with 1.0 plus zeros,
    accumulated in fp32.
    """
    rows = jax.lax.broadcasted_iota(jnp.int32, (a.shape[1], width), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (a.shape[1], width), 1)
    m = hit(rows, cols).astype(jnp.bfloat16)
    return jax.lax.dot_general(a.astype(jnp.bfloat16), m,
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dequant_planes(codes, scales, s_tensor):
    """codes [tn, tk/2] + scales [tn, >=tk/16] -> the BF16 weights of K's
    even and odd elements, two [tn, tk/2] planes.

    Byte j of a row holds element 2j in its low nibble and 2j+1 in its
    high one, so the planes are the nibbles as stored: the caller splits x
    into its even and odd columns instead of interleaving the weights.
    Only the per-block scale broadcast (one scale per 8 bytes) needs a lane
    map.  ``scales`` may be WIDER than tk/16 — the "lane128" layout pads
    each K-tile's strip to 128 lanes; only the leading tk/16 are read.
    """
    half = codes.shape[1]
    c = codes.astype(jnp.int32)           # Mosaic casts no uint8 to float
    s = _lane_map(scales.astype(jnp.float32), half,
                  lambda i, j: j // (BLOCK // 2) == i) * s_tensor
    # apply two-level scales, then round to BF16 — the MXU operand precision,
    # and exactly the values the QDQ serving path stores
    return ((_nibble_to_f32(c & 0xF) * s).astype(jnp.bfloat16),
            (_nibble_to_f32(c >> 4) * s).astype(jnp.bfloat16))


def _tile_dot(x_even, x_odd, codes, scales, s_tensor):
    """One K tile's fp32 contribution to x @ W^T: x_even/x_odd [tm, tk/2]
    are x's even and odd columns, codes/scales the weight tile."""
    lo, hi = _dequant_planes(codes, scales, s_tensor)
    f32 = jnp.float32
    dims = (((1,), (1,)), ((), ()))
    return (jax.lax.dot_general(x_even.astype(f32), lo.astype(f32), dims,
                                preferred_element_type=f32)
            + jax.lax.dot_general(x_odd.astype(f32), hi.astype(f32), dims,
                                  preferred_element_type=f32))


def _matmul_kernel(s_tensor_ref, xe_ref, xo_ref, codes_ref, scales_ref,
                   o_ref, acc_ref, *, n_k_steps: int):
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _tile_dot(xe_ref[...], xo_ref[...], codes_ref[...],
                              scales_ref[...], s_tensor_ref[0, 0])

    @pl.when(k_step == n_k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def swizzle_scales(scales: jax.Array, tile_k: int) -> jax.Array:
    """Relayout block scales [..., K/16] to the lane-aligned Mosaic layout.

    Compact [..., K/16] strips put only tile_k/16 values (32 for the default
    tile_k=512) on the TPU lane dimension — a sub-lane-width operand Mosaic
    would have to mask-pad on every tile fetch.  The "lane128" layout gives
    each K-tile a full 128-lane strip: tile ki's scales live at lanes
    [ki*128, ki*128 + tile_k/16), zero-padded to 128.  ``_dequant_planes``
    reads only the leading tile_k/16 lanes of its strip, so the kernel body
    is layout-agnostic and the swizzle is a pure host-side relayout (done
    once at weight-load time on TPU; the interpret path keeps compact).
    """
    tkb = tile_k // BLOCK
    assert tkb <= 128, f"tile_k {tile_k} puts {tkb} > 128 scales on a lane"
    *lead, kb = scales.shape
    nk = -(-kb // tkb)                        # K tiles (kb already padded)
    pad = nk * tkb - kb
    if pad:
        scales = jnp.pad(scales, [(0, 0)] * len(lead) + [(0, pad)])
    s = scales.reshape(*lead, nk, tkb)
    s = jnp.pad(s, [(0, 0)] * (len(lead) + 1) + [(0, 128 - tkb)])
    return s.reshape(*lead, nk * 128)


def _resolve_scale_layout(scale_layout: str | None, interpret: bool) -> str:
    """Default layout per target: Mosaic lowering wants lane-aligned scale
    strips ("lane128"); interpret mode keeps the compact [N, K/16]."""
    if scale_layout is None:
        return "compact" if interpret else "lane128"
    if scale_layout not in ("compact", "lane128"):
        raise ValueError(f"unknown scale_layout {scale_layout!r}")
    return scale_layout


@functools.partial(jax.jit, static_argnames=("tile_m", "tile_n", "tile_k",
                                             "out_dtype", "interpret",
                                             "scale_layout"))
def nvfp4_matmul(x: jax.Array, packed: PackedNVFP4, *,
                 tile_m: int = 128, tile_n: int = 256, tile_k: int = 512,
                 out_dtype=jnp.bfloat16, interpret: bool = True,
                 scale_layout: str | None = None) -> jax.Array:
    """y = x @ W where W is stored packed-NVFP4 as W^T:[N,K].

    Leading dims of x are flattened into M; x's last dim is the logical
    (un-padded) K and may be smaller than the stored K.  Shapes need not be
    tile multiples — tiles are shrunk to the (sublane, lane)-aligned
    envelope of the problem and inputs are zero-padded to tile multiples, so
    M=1 decode and odd K/N sizes work.

    ``scale_layout``: "compact" feeds the scales as stored ([N, K/16]);
    "lane128" relayouts them through ``swizzle_scales`` so each K-tile's
    strip is 128-lane aligned (the Mosaic lowering layout).  ``None`` picks
    by target: compact when interpreting, lane128 when lowering.  Both
    layouts are bit-identical in output — the kernel reads the same values.
    """
    *lead, k = x.shape
    xm = x.reshape(-1, k)
    m = xm.shape[0]
    n = packed.codes.shape[0]
    kp = packed.codes.shape[1] * 2               # stored (block-padded) K
    assert (packed.orig_k or kp) == k, "weight K mismatch"
    if kp > k:
        xm = jnp.pad(xm, ((0, 0), (0, kp - k)))  # pad codes are zero

    def rup(v, mult):
        return v + (-v) % mult

    # shrink tiles to the problem, but keep TPU (sublane, lane) alignment:
    # fp32 x/out tiles want (8, 128); the K tile must stay a BLOCK multiple
    tm = min(tile_m, rup(m, 8))
    tn = min(tile_n, rup(n, 128))
    tk = min(tile_k, rup(kp, 128))
    pm, pn, pk = (-m) % tm, (-n) % tn, (-kp) % tk
    if pm or pk:
        xm = jnp.pad(xm, ((0, pm), (0, pk)))
    codes, scales = packed.codes, packed.scales
    if pn or pk:
        codes = jnp.pad(codes, ((0, pn), (0, pk // 2)))
        scales = jnp.pad(scales, ((0, pn), (0, pk // BLOCK)))

    layout = _resolve_scale_layout(scale_layout, interpret)
    if layout == "lane128":
        scales = swizzle_scales(scales, tk)
        sk = 128
    else:
        sk = tk // BLOCK

    mm, nn, kk = xm.shape[0], codes.shape[0], xm.shape[1]
    grid = (nn // tn, mm // tm, kk // tk)        # K innermost for accumulation
    # accepts a scalar or any size-1 tensor_scale (a scan-sliced [1, 1] slab)
    s_tensor = packed.tensor_scale.astype(jnp.float32).reshape(1, 1)

    out = pl.pallas_call(
        functools.partial(_matmul_kernel, n_k_steps=kk // tk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda ni, mi, ki: (0, 0)),
            pl.BlockSpec((tm, tk // 2), lambda ni, mi, ki: (mi, ki)),
            pl.BlockSpec((tm, tk // 2), lambda ni, mi, ki: (mi, ki)),
            pl.BlockSpec((tn, tk // 2), lambda ni, mi, ki: (ni, ki)),
            pl.BlockSpec((tn, sk), lambda ni, mi, ki: (ni, ki)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda ni, mi, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((mm, nn), out_dtype),
        # fp32 accumulator tile lives in VMEM across the K loop
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        interpret=interpret,
    )(s_tensor, xm[:, 0::2], xm[:, 1::2], codes, scales)

    if pm or pn:
        out = out[:m, :n]
    return out.reshape(*lead, n)


# ---------------------------------------------------------------------------
# grouped GEMM: one launch for a whole stack of per-group skinny matmuls
# ---------------------------------------------------------------------------


def _grouped_kernel(s_tensor_ref, xe_ref, xo_ref, codes_ref, scales_ref,
                    o_ref, acc_ref, *, n_k_steps: int):
    k_step = pl.program_id(3)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _tile_dot(xe_ref[0], xo_ref[0], codes_ref[0],
                              scales_ref[0], s_tensor_ref[0, 0, 0])

    @pl.when(k_step == n_k_steps - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile_m", "tile_n", "tile_k",
                                             "out_dtype", "interpret",
                                             "scale_layout"))
def nvfp4_matmul_grouped(x: jax.Array, packed: PackedNVFP4, *,
                         tile_m: int = 128, tile_n: int = 256,
                         tile_k: int = 512, out_dtype=jnp.bfloat16,
                         interpret: bool = True,
                         scale_layout: str | None = None) -> jax.Array:
    """y[g] = x[g] @ W_g for a packed weight stack W^T:[G, N, K] — ONE
    ``pallas_call`` with a group grid dim instead of G dequant+einsum
    launches.

    This is the MoE decode GEMM: x [G, M, K] holds every active slot's
    token rows routed to expert g (M is tiny at decode), and the unfused
    path would dequantize ALL G expert slabs to BF16 in HBM every step —
    exactly the 4x weight-traffic blowup packed serving exists to avoid.
    Here each (g, n, k) weight tile is unpacked in VMEM and consumed in
    place, so HBM traffic stays at the packed 0.5625 B/param.

    ``packed.tensor_scale`` is one scale per group ([G, 1, 1], the
    ``pack(..., n_lead=1)`` layout) or one shared scale for the whole stack
    ([1, 1, 1], broadcast here).  Tiling/padding rules and ``scale_layout``
    are ``nvfp4_matmul``'s.
    """
    g, m, k = x.shape
    n = packed.codes.shape[1]
    kp = packed.codes.shape[2] * 2
    assert (packed.orig_k or kp) == k, "weight K mismatch"
    xm = x
    if kp > k:
        xm = jnp.pad(xm, ((0, 0), (0, 0), (0, kp - k)))

    def rup(v, mult):
        return v + (-v) % mult

    tm = min(tile_m, rup(m, 8))
    tn = min(tile_n, rup(n, 128))
    tk = min(tile_k, rup(kp, 128))
    pm, pn, pk = (-m) % tm, (-n) % tn, (-kp) % tk
    if pm or pk:
        xm = jnp.pad(xm, ((0, 0), (0, pm), (0, pk)))
    codes, scales = packed.codes, packed.scales
    if pn or pk:
        codes = jnp.pad(codes, ((0, 0), (0, pn), (0, pk // 2)))
        scales = jnp.pad(scales, ((0, 0), (0, pn), (0, pk // BLOCK)))

    layout = _resolve_scale_layout(scale_layout, interpret)
    if layout == "lane128":
        scales = swizzle_scales(scales, tk)
        sk = 128
    else:
        sk = tk // BLOCK

    mm, nn, kk = xm.shape[1], codes.shape[1], xm.shape[2]
    grid = (g, nn // tn, mm // tm, kk // tk)
    # per-group scales when the stack was packed with n_lead=1 ([G, 1, 1]);
    # a shared whole-stack scale ([1, 1, 1], n_lead=0) broadcasts to every
    # group
    s_tensor = jnp.broadcast_to(
        packed.tensor_scale.astype(jnp.float32).reshape(-1, 1, 1), (g, 1, 1))

    out = pl.pallas_call(
        functools.partial(_grouped_kernel, n_k_steps=kk // tk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1), lambda gi, ni, mi, ki: (gi, 0, 0)),
            pl.BlockSpec((1, tm, tk // 2),
                         lambda gi, ni, mi, ki: (gi, mi, ki)),
            pl.BlockSpec((1, tm, tk // 2),
                         lambda gi, ni, mi, ki: (gi, mi, ki)),
            pl.BlockSpec((1, tn, tk // 2),
                         lambda gi, ni, mi, ki: (gi, ni, ki)),
            pl.BlockSpec((1, tn, sk), lambda gi, ni, mi, ki: (gi, ni, ki)),
        ],
        out_specs=pl.BlockSpec((1, tm, tn),
                               lambda gi, ni, mi, ki: (gi, mi, ni)),
        out_shape=jax.ShapeDtypeStruct((g, mm, nn), out_dtype),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        interpret=interpret,
    )(s_tensor, xm[..., 0::2], xm[..., 1::2], codes, scales)

    if pm or pn:
        out = out[:, :m, :n]
    return out


# ---------------------------------------------------------------------------
# tensor-parallel dispatch: shard_map the kernel over per-shard weight tiles
# ---------------------------------------------------------------------------


def nvfp4_matmul_tp(x: jax.Array, packed: PackedNVFP4, mesh,
                    parallelism: str, *, axis: str = "model",
                    out_dtype=jnp.bfloat16, interpret: bool = True,
                    **tile_kw) -> jax.Array:
    """``y = x @ W`` with the packed weight partitioned over ``mesh[axis]``.

    Each shard runs the SAME Pallas kernel on its local codes/scales tile —
    a ``pallas_call`` cannot be partitioned by GSPMD, so the sharding seam
    is an explicit ``shard_map`` and the collective is chosen here:

      * ``"column"`` — W^T rows (the output dim N) are split; x is
        replicated into every shard, outputs stay N-sharded (no collective;
        the caller's next constraint/GEMM consumes the feature-sharded
        activation).  Every output element sees the full K, so numerics are
        identical to the single-device kernel.
      * ``"row"`` — the packed K dim is split in whole 16-element blocks;
        x arrives feature-sharded (the natural layout after a column-
        parallel layer + head-local attention), each shard contracts its K
        slice in fp32 and the partials are ``psum`` across ``axis``.

    Eligibility (divisibility, no K padding) is ``nvfp4.tp_shard_mode``;
    callers must have checked it.  Inputs not already laid out as
    ``in_specs`` are resharded by GSPMD — correctness never depends on the
    caller's placement, only zero-comm efficiency does.
    """
    from jax.sharding import PartitionSpec as P

    *lead, k = x.shape
    xm = x.reshape(-1, k)
    n = packed.codes.shape[0]
    s_tensor = packed.tensor_scale.astype(jnp.float32).reshape(1, 1)

    if parallelism == "column":
        in_specs = (P(), P(axis, None), P(axis, None), P())
        out_specs = P(None, axis)

        def local(xl, codes, scales, ts):
            p = PackedNVFP4(codes, scales, ts, orig_k=packed.orig_k)
            return nvfp4_matmul(xl, p, out_dtype=out_dtype,
                                interpret=interpret, **tile_kw)

        y = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                          out_specs=out_specs, check_vma=False)(
            xm, packed.codes, packed.scales, s_tensor)
    elif parallelism == "row":
        n_shards = int(dict(mesh.shape)[axis])
        local_k = packed.k // n_shards
        in_specs = (P(None, axis), P(None, axis), P(None, axis), P())
        out_specs = P()

        def local(xl, codes, scales, ts):
            p = PackedNVFP4(codes, scales, ts, orig_k=local_k)
            # fp32 partials so the only cross-shard numeric difference vs a
            # single device is the one psum reassociation
            part = nvfp4_matmul(xl, p, out_dtype=jnp.float32,
                                interpret=interpret, **tile_kw)
            return jax.lax.psum(part, axis)

        y = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                          out_specs=out_specs, check_vma=False)(
            xm, packed.codes, packed.scales, s_tensor)
        y = y.astype(out_dtype)
    else:
        raise ValueError(f"unknown parallelism {parallelism!r}")
    return y.reshape(*lead, n)
