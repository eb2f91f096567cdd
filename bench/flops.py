"""Operations and bytes the algorithm needs, computed from shapes.

These are the numerators of every utilization the benchmark reports.  They
count the work the model needs, not what a kernel happens to execute: a
causal query attends to the keys before it, a decode step reads the live
context's KV (not the whole page strip the slot holds), and recomputed
forwards (remat) are not counted.

``dims`` is a configuration's model dict (``configs/<name>.json``
"model"): ``d_model``, ``n_layers``, ``n_heads``, ``n_kv_heads``,
``head_dim``, ``d_ff``, ``vocab_size``.
"""
from __future__ import annotations

# NVFP4 storage: a 4-bit code per element, one E4M3 scale per 16 elements.
NVFP4_CODE_BYTES = 0.5
NVFP4_SCALE_BYTES = 1.0 / 16


def layer_gemms(dims: dict) -> dict:
    """(K, N) of each GEMM of one decoder layer (SwiGLU MLP, fused QKV)."""
    d, hd = dims["d_model"], dims["head_dim"]
    h, hkv, ff = dims["n_heads"], dims["n_kv_heads"], dims["d_ff"]
    return {"wqkv": (d, (h + 2 * hkv) * hd), "wo": (h * hd, d),
            "wg": (d, ff), "wu": (d, ff), "wd": (ff, d)}


def matmul_params(dims: dict) -> int:
    """Weights that take part in a matmul per token: every layer's GEMMs
    and the output projection (the embedding gather is not a matmul)."""
    per_layer = sum(k * n for k, n in layer_gemms(dims).values())
    return dims["n_layers"] * per_layer + dims["d_model"] * dims["vocab_size"]


def attention_flops(dims: dict, n_keys: float) -> float:
    """One query attending to ``n_keys`` keys in every layer: QK^T and PV,
    two FLOPs per multiply-add."""
    return 4.0 * dims["n_layers"] * dims["n_heads"] * dims["head_dim"] * n_keys


def gemm_call(m: int, k: int, n: int, act_bytes: int = 2,
              out_bytes: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of one packed NVFP4 GEMM call: x [m, k] times a
    packed [k, n] weight.  Bytes: the packed codes, the block scales, the
    tensor scale, x and the output."""
    flops = 2.0 * m * k * n
    w = k * n * (NVFP4_CODE_BYTES + NVFP4_SCALE_BYTES) + 4
    return flops, w + m * k * act_bytes + m * n * out_bytes


def paged_attention_call(dims: dict, contexts, kv_bytes: int = 2
                         ) -> tuple[float, float]:
    """(FLOPs, bytes) of one decode step's attention over every layer:
    each active slot's query attends to its live context (``contexts``
    holds the number of keys of each).  Bytes are the live KV that the
    algorithm has to read, plus the queries and the outputs."""
    hd, h, hkv = dims["head_dim"], dims["n_heads"], dims["n_kv_heads"]
    keys = float(sum(contexts))
    flops = attention_flops(dims, keys)
    kv = keys * 2 * hkv * hd * kv_bytes * dims["n_layers"]
    qo = len(contexts) * 2 * h * hd * 2 * dims["n_layers"]
    return flops, kv + qo


def decode_step(dims: dict, contexts) -> float:
    """Model FLOPs of one decode step: two FLOPs per matmul weight for each
    active token, plus attention over its live context."""
    return (2.0 * matmul_params(dims) * len(contexts)
            + attention_flops(dims, float(sum(contexts))))


def qad_step_per_token(dims: dict, seq_len: int) -> float:
    """Model FLOPs per token of a QAD step at ``seq_len``: the teacher's
    forward (2N), the student's forward and backward (6N), and causal
    attention in each (a query at position i attends to i + 1 keys, so to
    (seq_len + 1) / 2 on average)."""
    fwd = 2.0 * matmul_params(dims) + attention_flops(dims,
                                                      (seq_len + 1) / 2.0)
    return 4.0 * fwd
