"""Parameter-spec system: shapes + logical sharding axes + quant kinds.

Every model describes its parameters as a pytree of ``ParamSpec``.  From one
spec tree we derive:

  * ``init_params``        — materialized, randomly initialized params
  * ``abstract_params``    — ShapeDtypeStructs (+ NamedSharding) for the
                             multi-pod dry-run (no allocation)
  * PTQ quantization       — ``kind`` + ``contract_axis`` say how each GEMM
                             weight is blocked
  * sharding               — logical axes resolved against a mesh by
                             ``repro.distributed.sharding``
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import numerics as obs_numerics


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: tuple
    axes: tuple                 # logical axis names, len == len(shape)
    dtype: Any = jnp.bfloat16
    init: str = "normal"        # normal | zeros | ones | embed | lru_lambda
    scale: float = 1.0          # multiplier on the default init std
    kind: str = ""              # quant kind ("mlp"|"attn"|...) if a GEMM weight
    contract_axis: int = 0      # which axis is the GEMM contraction dim

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _init_one(spec: ParamSpec, key) -> jax.Array:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    if spec.init == "lru_lambda":
        # RG-LRU: Λ init so that a = exp(-softplus(Λ)·c·σ(..)) starts ~0.9-0.999
        u = jax.random.uniform(key, spec.shape, jnp.float32, 0.9, 0.999)
        lam = jnp.log(jnp.exp(-jnp.log(u) / 8.0) - 1.0)   # softplus^-1
        return lam.astype(spec.dtype)
    fan_in = spec.shape[spec.contract_axis] if len(spec.shape) else 1
    std = spec.scale * (0.02 if spec.init == "embed" else 1.0 / np.sqrt(max(fan_in, 1)))
    return (jax.random.normal(key, spec.shape, jnp.float32) * std).astype(spec.dtype)


def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def init_params(specs, rng) -> Any:
    leaves, treedef = jax.tree.flatten(specs, is_leaf=is_spec)
    keys = jax.random.split(rng, len(leaves))
    return jax.tree.unflatten(treedef, [_init_one(s, k) for s, k in zip(leaves, keys)])


def abstract_params(specs, sharding_fn: Callable | None = None) -> Any:
    """ShapeDtypeStruct tree; ``sharding_fn(spec) -> NamedSharding | None``."""
    def one(s: ParamSpec):
        sh = sharding_fn(s) if sharding_fn else None
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh)
    return jax.tree.map(one, specs, is_leaf=is_spec)


def stack_specs(spec_tree, n: int, axis_name: str = "layers"):
    """Prepend a stacked [n, ...] dim to every spec (scan-over-layers)."""
    def one(s: ParamSpec):
        return dataclasses.replace(
            s, shape=(n, *s.shape), axes=(axis_name, *s.axes),
            contract_axis=s.contract_axis + 1 if s.kind else s.contract_axis)
    return jax.tree.map(one, spec_tree, is_leaf=is_spec)


def param_count(specs) -> int:
    return sum(int(np.prod(s.shape)) for s in jax.tree.leaves(specs, is_leaf=is_spec))


def spec_bytes(specs) -> int:
    """Total bytes of a ParamSpec tree (abstract pricing — no allocation)."""
    return sum(int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize
               for s in jax.tree.leaves(specs, is_leaf=is_spec))


def weight_stats(params) -> dict:
    """Weight-memory accounting over a (possibly mixed) parameter pytree.

    Understands both dense ``jax.Array`` leaves and ``PackedNVFP4`` nodes
    (whose codes + block scales + tensor scale are charged together), so the
    serve driver can report the true deployed footprint:

      q_params / q_bytes         — elements / bytes of quantized-GEMM weights
      dense_params / dense_bytes — everything kept dense
      total_bytes                — q_bytes + dense_bytes
    """
    from repro.core.nvfp4 import PackedNVFP4

    stats = {"q_params": 0, "q_bytes": 0, "dense_params": 0, "dense_bytes": 0}

    def one(leaf):
        if isinstance(leaf, PackedNVFP4):
            stats["q_params"] += int(np.prod(leaf.shape))
            stats["q_bytes"] += int(leaf.nbytes)
        else:
            stats["dense_params"] += int(np.prod(leaf.shape))
            stats["dense_bytes"] += int(leaf.nbytes)
        return leaf

    jax.tree.map(one, params,
                 is_leaf=lambda l: isinstance(l, PackedNVFP4))
    stats["total_bytes"] = stats["q_bytes"] + stats["dense_bytes"]
    return stats


def zeros_from_specs(specs):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), specs,
                        is_leaf=is_spec)


def merge_slot_state(specs, old, new, active):
    """Keep inactive slots' state bit for bit across a batched decode step.

    ``specs`` names each leaf's "batch" axis; ``active`` [n_slots] selects
    per-slot between the freshly computed leaf and the previous one.  The
    select is exact (no arithmetic), so active rows carry the new values
    unchanged and inactive rows are indistinguishable from never stepping.
    """
    def one(spec, o, n):
        ax = spec.axes.index("batch")
        act = active.reshape((1,) * ax + (-1,) + (1,) * (n.ndim - ax - 1))
        return jnp.where(act, n.astype(o.dtype), o)
    return jax.tree.map(one, specs, old, new, is_leaf=is_spec)


# ---------------------------------------------------------------------------
# scan-over-layers with selective quantization (paper §3.4)
# ---------------------------------------------------------------------------


def scan_layers(body_fn, carry, stacked_params, stacked_xs, qcfg,
                skip_first: int = 0, skip_last: int = 0, remat: str = "none"):
    """``jax.lax.scan`` over stacked layer params, in up to three segments.

    ``body_fn(qcfg)(carry, (params_slice, xs_slice)) -> (carry, ys)``.
    The first/last ``skip_*`` layers run with quantization disabled
    (BF16 segments of the paper's selective recipe); the middle segment uses
    ``qcfg``.  Segments are separate scans — the layer body is compiled once
    per segment, keeping HLO size O(1) in depth.

    ``stacked_params`` may mix dense leaves with ``PackedNVFP4`` nodes
    (packed serving weights): both the segment slicing below and the scan
    itself operate on the underlying array leaves, all of which carry the
    stacked [n, ...] leading dim (PTQ gives packed leaves per-layer tensor
    scales shaped [n, 1, ...] for exactly this reason), so the body receives
    per-layer ``PackedNVFP4`` slices with their static metadata intact.

    Carried-state form: state that a step updates a few rows of per layer
    (the paged KV pool) rides in ``carry`` beside the activations, and
    ``stacked_xs=jnp.arange(n)`` hands the body its absolute layer index
    (the segments slice it like the params, so the count runs across the
    skip segments); the body writes at ``[layer, ...]`` of the whole
    buffer and returns ``ys=None``.  Scanning such state as ``xs``/``ys``
    instead makes XLA slice every layer out, scatter into the slice, write
    it to a separate ``ys`` buffer and copy that into the output: a second
    copy of the state and a whole-layer copy per layer per step.  In the
    carry, the buffer is updated in place.
    """
    from repro.core.qconfig import BF16

    leaves = jax.tree.leaves(stacked_params)
    n = leaves[0].shape[0]
    skip_first = min(skip_first, n)
    skip_last = min(skip_last, n - skip_first)
    # numerics probes: only when the policy opts in AND a tape is
    # installed (both trace-time checks — the off path is unchanged).
    # Skip segments keep the numerics flag so per-layer probes that are
    # not quantization-gated (the decoder's hidden-state tap) still
    # cover BF16 layers; quant probes stay silent there because
    # ``quantizes()`` is False.
    tape = obs_numerics.active() if getattr(qcfg, "numerics", False) else None
    skip_qc = (dataclasses.replace(BF16, numerics=True)
               if tape is not None else BF16)
    bounds = [(0, skip_first, skip_qc), (skip_first, n - skip_last, qcfg),
              (n - skip_last, n, skip_qc)]

    ys_all, probes_all = [], []
    for lo, hi, qc in bounds:
        if hi <= lo:
            continue
        seg_p = jax.tree.map(lambda a: a[lo:hi], stacked_params)
        seg_x = jax.tree.map(lambda a: a[lo:hi], stacked_xs) if stacked_xs is not None else None
        fn = body_fn(qc)
        if tape is not None:
            fn = _probe_scoped(fn, tape)
        if remat != "none":
            policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                      if remat == "dots" else None)
            fn = jax.checkpoint(fn, policy=policy)
        carry, ys = jax.lax.scan(fn, carry, (seg_p, seg_x))
        if tape is not None:
            ys, probes = ys
            probes_all.append((probes, hi - lo))
        ys_all.append(ys)
    if tape is not None:
        for site, stats in _merge_probes(probes_all).items():
            tape.put(f"layers.{site}", stats)
    if not any(jax.tree.leaves(y) for y in ys_all):
        ys = None
    elif len(ys_all) > 1:
        ys = jax.tree.map(lambda *a: jnp.concatenate(a, 0), *ys_all)
    else:
        ys = ys_all[0]
    return carry, ys


def _probe_scoped(fn, tape):
    """Ride the layer body's numerics probes out through the scan ``ys``.

    Pushes a tape scope around each body trace so per-layer probe puts
    stay separable from the enclosing forward's, then returns them as an
    extra ``ys`` component: ``jax.lax.scan`` stacks each probe scalar
    into a per-layer ``[seg_len]`` series.  Composes with
    ``jax.checkpoint`` (applied outside): the backward retrace pushes and
    pops its own balanced scope.
    """
    def wrapped(carry, inp):
        tape.push_scope()
        try:
            carry, y = fn(carry, inp)
        finally:
            probes = tape.pop_scope()
        return carry, (y, probes)
    return wrapped


def _merge_probes(segs):
    """Key-union merge of per-segment scan probes into [n_layers] series.

    ``segs``: list of ``(probes_dict, seg_len)`` in layer order.  BF16
    skip segments record no quant probes, so sites missing from a
    segment are NaN-filled for its layers — the host-side recorder
    treats NaN as "layer not probed" and the per-layer series keeps a
    stable length of ``n_layers``.
    """
    sites = sorted({s for d, _ in segs for s in d})
    out = {}
    for site in sites:
        stats = sorted({k for d, _ in segs if site in d for k in d[site]})
        out[site] = {}
        for st in stats:
            first = next(d[site][st] for d, _ in segs
                         if site in d and st in d[site])
            rest = first.shape[1:]
            parts = [d[site][st].astype(jnp.float32) if site in d
                     and st in d[site]
                     else jnp.full((ln, *rest), jnp.nan, jnp.float32)
                     for d, ln in segs]
            out[site][st] = (jnp.concatenate(parts, 0) if len(parts) > 1
                             else parts[0])
    return out
