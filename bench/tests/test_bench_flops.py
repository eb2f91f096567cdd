"""bench/flops.py and bench/peaks.py against counts made by hand."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import flops  # noqa: E402
import peaks  # noqa: E402

QWEN = {"d_model": 1024, "n_layers": 24, "n_heads": 16, "n_kv_heads": 16,
        "head_dim": 64, "d_ff": 2816, "vocab_size": 151936}
ACEREASON = {"d_model": 3584, "n_layers": 28, "n_heads": 28,
             "n_kv_heads": 4, "head_dim": 128, "d_ff": 18944,
             "vocab_size": 152064}


@pytest.mark.parametrize("dims,want", [
    # 24 x (1024*3072 + 1024*1024 + 3*1024*2816) + 1024*151936
    (QWEN, 24 * 12_845_056 + 155_582_464),
    # 28 x (3584*4608 + 3584*3584 + 3*3584*18944) + 3584*152064
    (ACEREASON, 28 * 233_046_016 + 544_997_376),
])
def test_matmul_params(dims, want):
    assert flops.matmul_params(dims) == want


def test_gemm_call_counts_packed_weight_x_and_out():
    f, b = flops.gemm_call(32, 1024, 3072)
    assert f == 2 * 32 * 1024 * 3072
    # codes 0.5 B + scales 1/16 B per weight, tensor scale, bf16 x and out
    assert b == 1024 * 3072 * 0.5625 + 4 + 32 * 1024 * 2 + 32 * 3072 * 2


def test_attention_reads_the_live_context():
    f, b = flops.paged_attention_call(QWEN, [100, 300])
    assert f == 4 * 24 * 16 * 64 * 400
    assert b == 400 * 2 * 16 * 64 * 2 * 24 + 2 * 2 * 16 * 64 * 2 * 24
    # GQA: acereason reads 4 KV heads of 128 for 28 query heads
    f, b = flops.paged_attention_call(dict(ACEREASON, n_layers=1), [10])
    assert f == 4 * 28 * 128 * 10
    assert b == 10 * 2 * 4 * 128 * 2 + 2 * 28 * 128 * 2


def test_decode_step_and_qad_step():
    n = flops.matmul_params(QWEN)
    assert flops.decode_step(QWEN, [10, 20]) == \
        2 * n * 2 + 4 * 24 * 16 * 64 * 30
    # teacher forward 2N, student forward and backward 6N, causal
    # attention over (4096 + 1) / 2 keys on average in each
    fwd = 2 * n + 4 * 24 * 16 * 64 * 2048.5
    assert flops.qad_step_per_token(QWEN, 4096) == 4 * fwd
    assert flops.qad_step_per_token(QWEN, 4096) == 4_516_413_440


def test_peaks_by_device_kind():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_s"] == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")
