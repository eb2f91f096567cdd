"""Roofline terms for a TPU chip from a compiled dry-run cell.

    compute_s    = FLOPs_per_chip / peak_flops     (bf16 MXU peak)
    memory_s     = HBM_bytes_per_chip / hbm_bw
    collective_s = collective_bytes_per_chip / ici_bw (per link)

with the chip's published peaks from ``HW``, keyed by jax's
``device_kind``.

FLOPs/bytes come from the HLO parser (``hlo_analysis`` — scan-aware), with
``compiled.cost_analysis()`` reported alongside as a cross-check.
MODEL_FLOPS is the analytic useful-work number (6·N·D train / 2·N_active·D
decode); its ratio to HLO FLOPs exposes remat & padding waste.
"""
from __future__ import annotations

import dataclasses

from repro.configs import ModelConfig, ShapeConfig

# Published per-chip peaks keyed by ``jax.Device.device_kind``.  TPU v5e
# ("TPU v5 lite"): Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16,
# 16 GiB HBM at 819 GB/s, 1,600 Gbit/s ICI over 4 links (50 GB/s per link).
HW = {
    "TPU v5 lite": {
        "peak_flops": 197e12,        # bf16 / chip
        "hbm_bw": 819e9,             # bytes/s
        "ici_bw": 50e9,              # bytes/s/link
        "hbm_cap": 16 * 2**30,       # bytes
    },
}


def hw(device_kind: str) -> dict:
    """Published peaks of ``device_kind``; a kind not in ``HW`` is an error
    (a roofline against some other chip's peaks would be silently wrong)."""
    try:
        return HW[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device_kind "
                         f"{device_kind!r}; known: {sorted(HW)}") from None


@dataclasses.dataclass
class Roofline:
    compute_s: float
    memory_s: float
    collective_s: float
    model_flops_per_chip: float
    hlo_flops_per_chip: float
    bytes_per_chip: float
    coll_bytes_per_chip: float
    dominant: str
    useful_ratio: float          # MODEL_FLOPS / HLO_FLOPS
    step_s: float                # max of the three terms (no-overlap bound)
    mfu: float                   # model_flops / (step_s * peak)

    def as_dict(self):
        return dataclasses.asdict(self)


def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """Analytic useful FLOPs per step (global, all chips)."""
    n_active = cfg.n_params(active_only=True)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        flops = 6.0 * n_active * tokens           # student fwd+bwd
        flops += 2.0 * n_active * tokens          # teacher fwd (QAD)
        flops += _attn_flops(cfg, shape.seq_len, tokens, train=True)
        return flops
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens + _attn_flops(cfg, shape.seq_len,
                                                     tokens, train=False)
    # decode: one token per sequence against a seq_len cache
    tokens = shape.global_batch
    flops = 2.0 * n_active * tokens
    flops += _attn_decode_flops(cfg, shape.seq_len, shape.global_batch)
    return flops


def _n_attn_layers(cfg) -> int:
    if cfg.family == "rglru_hybrid":
        return cfg.n_layers // cfg.attn_period
    if cfg.family == "rwkv6":
        return 0
    if cfg.family == "encdec":
        return cfg.n_layers * 2 + cfg.n_enc_layers
    return cfg.n_layers


def _attn_flops(cfg, seq, tokens, train: bool) -> float:
    """Quadratic attention score+value FLOPs (not in 6·N·D)."""
    n_l = _n_attn_layers(cfg)
    eff = min(seq, cfg.window) if cfg.window else seq
    per_tok = 2 * 2 * cfg.n_heads * cfg.head_dim * eff / 2   # qk + pv, causal
    mult = 3 if train else 1
    extra = 1 + (1 / 3 if train else 0)     # QAD teacher fwd on top of 3x
    return n_l * tokens * per_tok * mult * (extra if train else 1)


def _attn_decode_flops(cfg, cache_len, batch) -> float:
    n_l = _n_attn_layers(cfg)
    eff = min(cache_len, cfg.window) if cfg.window else cache_len
    return n_l * batch * 2 * 2 * cfg.n_heads * cfg.head_dim * eff


def compute(cfg: ModelConfig, shape: ShapeConfig, hlo_stats: dict,
            n_chips: int, device_kind: str) -> Roofline:
    peaks = hw(device_kind)
    mf_chip = model_flops(cfg, shape) / n_chips
    hf = hlo_stats["flops_per_device"]
    by = hlo_stats["bytes_per_device"]
    cb = hlo_stats["collective_bytes_per_device"]

    c_s = hf / peaks["peak_flops"]
    m_s = by / peaks["hbm_bw"]
    k_s = cb / peaks["ici_bw"]
    terms = {"compute": c_s, "memory": m_s, "collective": k_s}
    dominant = max(terms, key=terms.get)
    step = max(c_s, m_s, k_s)
    return Roofline(
        compute_s=c_s, memory_s=m_s, collective_s=k_s,
        model_flops_per_chip=mf_chip, hlo_flops_per_chip=hf,
        bytes_per_chip=by, coll_bytes_per_chip=cb,
        dominant=dominant,
        useful_ratio=mf_chip / hf if hf else 0.0,
        step_s=step,
        mfu=(mf_chip / peaks["peak_flops"]) / step if step else 0.0,
    )
