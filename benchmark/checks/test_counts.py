"""Operation counts on shapes worked by hand."""


import pytest

from benchmark import counts
from benchmark.peaks import peaks_for

SMALL = {"vocab": 10, "d_model": 4, "d_ff": 6, "n_layers": 2, "n_heads": 2, "n_kv_heads": 1, "head_dim": 2}


def test_matmul_params_dense():
    # a layer: wq 4x4 + wk 4x2 + wv 4x2 + wo 4x4 = 48, feed-forward 3x4x6 = 72; head 4x10
    assert counts.matmul_params_per_token(SMALL) == 2 * (48 + 72) + 40


def test_train_flops_of_two_documents():
    # attention: token i meets i+1 keys; documents of 3 and 1 tokens: 6 + 1 pairs,
    # each 2 products x 2 operations x 2 heads x 2 wide x 2 layers = 32
    fwd = 2 * 280 * 4 + 32 * 7
    assert counts.train_flops(SMALL, [3, 1]) == 3 * fwd


def test_published_sizes():
    mistral = {"vocab": 32768, "d_model": 4096, "d_ff": 14336, "n_layers": 32, "n_heads": 32,
               "n_kv_heads": 8, "head_dim": 128}
    # 7.25B parameters, less the 134M-row embedding that is a lookup
    assert counts.matmul_params_per_token(mistral) == 7_113_539_584


def test_peaks_table():
    assert peaks_for("TPU v5 lite") == {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
    with pytest.raises(KeyError):
        peaks_for("TPU v9000")
