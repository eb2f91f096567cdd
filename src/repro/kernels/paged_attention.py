"""Pallas TPU kernel: fused paged attention for the serving decode path.

The pool is stored as the kernel reads it: K and V pages
``[L, n_blocks, bs, Hkv*hd]`` (a row-major flat head axis, so a head's
``hd`` lanes stay together), FP8 scale planes ``[L, n_blocks, bs, Hkv]``.
The kernel takes the WHOLE pool plus a layer index, so the decode step can
carry the pool through its layer scan and update it in place: no layer
slice, no relayout and no second pool is ever materialized for the call.

The unfused hot path (``models.attention``) is a two-step:

    paged_gather_layer   — gather the layer's pool pages into a dense
                           per-request view [B, MB*bs, Hkv, hd],
                           dequantizing FP8 pages to BF16 on the way
    paged_attend         — repeat_kv + score/softmax/weighted-sum einsums

which materializes the gathered KV in HBM (reads every page, writes a dense
copy, reads it again) and runs the FP8 dequant as a separate elementwise
pass.  This kernel does page-table gather + FP8-KV dequant + attend in ONE
``pallas_call`` over the block table: the layer index and the per-request
block table ride in as scalar-prefetch operands, so each grid step's
``BlockSpec`` index map computes the page to DMA next — pages stream
HBM→VMEM exactly once and the dense intermediate never exists.

Both serving shapes share the kernel:

  * ``q_len == 1``   — the engine's one-token decode step,
  * ``q_len == k+1`` — the speculative verify step; per-query positions
    ``pos[b, i] = lens[b] + i + 1`` ARE the causal intra-chunk mask, exactly
    as in ``paged_attend``.

Parity contract (why softmax is exact, not flash-rescaled): the unfused
path is this kernel's oracle, and the engine's greedy tokens must not move
when fusion is switched on.  A running-rescale online softmax reassociates
the exp/sum arithmetic, which perturbs BF16 probabilities by 1 ulp often
enough to flip greedy argmaxes over a long decode.  Instead the kernel
streams pages in one pass, buffering the dequantized K and V pages in VMEM
scratch, and scores, masks and softmaxes the whole strip ONCE on the last
grid step — the associativity-sensitive math happens exactly once, in the
oracle's order, so BF16-KV greedy decode is bitwise-stable under fusion.
On the TPU that holds for k+1 verify; for one-token decode XLA lowers the
oracle's one-query dots as multiply+reduce instead of on the MXU, and the
two agree only within the bf16 roundings of p and of the output
(``chip_smoke.py`` checks both).  (A rescaling online softmax only wins
when the strip is too big for VMEM — the prefill regime, which
``blockwise_attention`` already covers.)

Mosaic layout: a block's two minor dims must be (8, 128)-aligned or whole,
so a grid step reads a page's [bs, g*hd] slab for the g KV heads that fill
128 lanes, and buffers it whole in a [s_alloc, g*hd] scratch strip, so no
lane is padding at hd 64.  The pool's flat head axis is what makes that
slab a plain block of the stored array: with a separate [.., Hkv, hd]
minor pair at hd 64 the TPU tiles the pool differently, and viewing a
layer as [n_blocks, bs, Hkv*hd] cost a relayout of the whole layer, for K
and for V, in every layer of every step.  The strips and the score and
probability rows must fit Mosaic's scoped VMEM (``vmem_bytes``): at
qwen1.5-0.5b's heads that holds to 24k context, and 32k does not compile,
so ``serve.Engine`` falls back to the two-step where ``fits_vmem`` says no.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# Mosaic's default scoped VMEM limit on TPU v5e
SCOPED_VMEM_BYTES = 16 * 2**20


def _heads_per_step(hkv: int, hd: int) -> int:
    """KV heads one grid step reads: the fewest whose [bs, g*hd] page slab
    is 128-lane aligned (Mosaic tiles a block's minor dim by 128 lanes, or
    takes it whole), else all of them."""
    for g in range(1, hkv + 1):
        if hkv % g == 0 and (g * hd) % 128 == 0:
            return g
    return hkv


def vmem_bytes(s_alloc: int, hkv: int, hd: int, rows: int,
               itemsize: int = 2) -> int:
    """VMEM one grid step holds: the K and V scratch strips [s_alloc, g*hd]
    plus, for each of its g heads, an fp32 score and probability strip
    [rows, s_alloc] (``rows`` = q heads per KV head x query length, padded
    to 8 sublanes).  Calibrated against the TPU v5e compiler: every shape
    it refused for lack of VMEM estimates above ``SCOPED_VMEM_BYTES``."""
    g = _heads_per_step(hkv, hd)
    lanes = -(-g * hd // 128) * 128
    sublanes = -(-rows // 8) * 8
    return 2 * s_alloc * lanes * itemsize + 2 * g * sublanes * s_alloc * 4


def fits_vmem(s_alloc: int, hkv: int, hd: int, rows: int) -> bool:
    return vmem_bytes(s_alloc, hkv, hd, rows) <= SCOPED_VMEM_BYTES


def _attend_kernel(bt_ref, pos_ref, layer_ref, q_ref, k_ref, v_ref, ks_ref,
                   vs_ref, o_ref, k_scr, v_scr, *, mb: int, bs: int,
                   s_q: int, g: int, window: int, fp8: bool):
    """grid (B, Hkv/g, MB); page j of the prefetched layer arrives via the
    scalar-prefetched table (``layer_ref`` is read by the index maps only).

    Each step buffers its page's K and V for the step's g heads; the last
    step attends over the whole buffered strip in the oracle's op order.
    """
    bi, hg, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    r, hd = q_ref.shape[-2:]
    page = pl.ds(pl.multiple_of(j * bs, bs), bs)
    if not fp8:
        k_scr[page, :] = k_ref[0].astype(k_scr.dtype)    # [bs, g*hd]
        v_scr[page, :] = v_ref[0].astype(v_scr.dtype)
    for c in range(g if fp8 else 0):
        lanes = slice(c * hd, (c + 1) * hd)
        # this head's scale column: a one-hot select (exact), since the
        # head index is dynamic and the scale block spans all heads
        head = jax.lax.broadcasted_iota(jnp.int32, ks_ref.shape[1:], 1)
        pick = (head == hg * g + c).astype(jnp.float32)
        ks = jnp.sum(ks_ref[0] * pick, axis=1, keepdims=True)
        vs = jnp.sum(vs_ref[0] * pick, axis=1, keepdims=True)
        k = k_ref[0, :, lanes].astype(jnp.float32) * ks  # [bs, hd]
        v = v_ref[0, :, lanes].astype(jnp.float32) * vs
        k_scr[page, lanes] = k.astype(k_scr.dtype)
        v_scr[page, lanes] = v.astype(v_scr.dtype)

    @pl.when(j == mb - 1)
    def _attend():
        # per-query valid-key counts -> the oracle's position mask; the
        # q rows are laid out [n_rep, s_q] so row i's query index is i % s_q
        row = jax.lax.broadcasted_iota(jnp.int32, (r, 1), 0) % s_q
        qpos = jnp.zeros((r, 1), jnp.int32)
        for t in range(s_q):
            qpos = jnp.where(row == t, pos_ref[bi, t], qpos)
        slot = jax.lax.broadcasted_iota(jnp.int32, (r, mb * bs), 1)
        valid = slot < qpos
        if window:
            valid &= slot >= qpos - window
        scale = 1.0 / jnp.sqrt(jnp.float32(hd))
        for c in range(g):
            lanes = slice(c * hd, (c + 1) * hd)
            q = q_ref[0, c]                              # [R, hd]
            sc = jax.lax.dot_general(q, k_scr[:, lanes],
                                     (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            sm = jnp.where(valid, sc * scale, NEG_INF)
            p = jax.nn.softmax(sm, axis=-1)
            out = jax.lax.dot_general(p.astype(q.dtype), v_scr[:, lanes],
                                      (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
            o_ref[0, c] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    block_tables: jax.Array, pos: jax.Array,
                    layer: jax.Array, k_scale: jax.Array | None = None,
                    v_scale: jax.Array | None = None, *,
                    window: int = 0, interpret: bool = True) -> jax.Array:
    """Fused gather+dequant+attend; drop-in for the gather/attend two-step.

    q: [B, S, H, hd]; pages: the whole pool, [L, n_blocks, bs, Hkv*hd]
    (+ optional fp32 [L, n_blocks, bs, Hkv] scale planes for FP8 pools);
    block_tables: [B, MB]; pos: [B] or [B, S] per-query valid-key counts,
    ``paged_attend`` semantics; layer: the (traced) layer index the pages
    are read from.  Returns [B, S, H, hd] in q's dtype.
    """
    b, s_q, h, hd = q.shape
    bs = k_pages.shape[2]
    hkv = k_pages.shape[3] // hd
    mb = block_tables.shape[1]
    n_rep = h // hkv
    r = n_rep * s_q
    fp8 = k_scale is not None
    g = _heads_per_step(hkv, hd)

    # head h = hkv_idx * n_rep + rep (repeat_kv layout) -> group by kv head
    q4 = q.reshape(b, s_q, hkv, n_rep, hd).transpose(0, 2, 3, 1, 4)
    q4 = q4.reshape(b, hkv, r, hd)
    pos = jnp.asarray(pos, jnp.int32)
    pos2 = jnp.broadcast_to(pos[:, None] if pos.ndim == 1 else pos, (b, s_q))
    bt = jnp.asarray(block_tables, jnp.int32)
    lyr = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))

    def page_map(bi, hi, ji, bt, pos, lyr):
        return (lyr[0], bt[bi, ji], 0, hi)

    def scale_map(bi, hi, ji, bt, pos, lyr):
        return (lyr[0], bt[bi, ji], 0, 0)

    def q_map(bi, hi, ji, bt, pos, lyr):
        return (bi, hi, 0, 0)

    # the layer dim is squeezed: the kernel sees one page, [1, bs, g*hd]
    in_specs = [
        pl.BlockSpec((1, g, r, hd), q_map),
        pl.BlockSpec((None, 1, bs, g * hd), page_map),
        pl.BlockSpec((None, 1, bs, g * hd), page_map),
    ]
    args = [q4, k_pages, v_pages]
    if fp8:
        in_specs += [pl.BlockSpec((None, 1, bs, hkv), scale_map)] * 2
        args += [k_scale, v_scale]
    else:
        # dummy scalars (kernel ignores them when fp8=False)
        in_specs += [pl.BlockSpec((1, 1),
                                  lambda bi, hi, ji, bt, pos, lyr: (0, 0))] * 2
        args += [jnp.zeros((1, 1), jnp.float32)] * 2

    kern = functools.partial(_attend_kernel, mb=mb, bs=bs, s_q=s_q, g=g,
                             window=window, fp8=fp8)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, hkv // g, mb),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, g, r, hd), q_map),
            scratch_shapes=[pltpu.VMEM((mb * bs, g * hd), q.dtype),
                            pltpu.VMEM((mb * bs, g * hd), q.dtype)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, r, hd), q.dtype),
        interpret=interpret,
    )(bt, pos2, lyr, *args)

    out = out.reshape(b, hkv, n_rep, s_q, hd).transpose(0, 3, 1, 2, 4)
    return out.reshape(b, s_q, h, hd)
