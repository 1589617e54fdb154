"""counts.py and stats.py against numbers worked by hand."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.harness import counts, stats  # noqa: E402

MISTRAL = json.loads((ROOT / "benchmarks/configs/mistral-7b-qlora.json").read_text())
#: Qwen/Qwen2-7B's published sizes (its cell is still open: PERF.md section 7)
QWEN = {"hidden_size": 3584, "intermediate_size": 18944, "num_attention_heads": 28,
        "num_hidden_layers": 28, "num_key_value_heads": 4, "vocab_size": 152064}


def test_mistral_matrix_parameters_by_hand():
    # q 4096x4096, k and v 4096x1024, o 4096x4096, three MLP 4096x14336
    per_layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert per_layer == 218_103_808
    assert counts.layer_matmul_params(MISTRAL) == per_layer
    assert counts.frozen_matmul_params(MISTRAL) == 32 * per_layer + 4096 * 32768
    # rank 16 on all seven projections: 16 * sum(in + out)
    lora_layer = 16 * (8192 + 5120 + 5120 + 8192 + 3 * 18432)
    assert counts.lora_params(MISTRAL) == 32 * lora_layer == 41_943_040


def test_qwen_matrix_parameters_by_hand():
    per_layer = 2 * 3584 * 3584 + 2 * 3584 * 512 + 3 * 3584 * 18944
    assert counts.layer_matmul_params(QWEN) == per_layer
    assert counts.frozen_matmul_params(QWEN) == 28 * per_layer + 3584 * 152064


def test_lora_training_flops_per_token_by_hand():
    # attention, forward, one sequence one layer at 2048: 4*S^2*H*D / 2
    fwd = 4 * 2048 * 2048 * 32 * 128 / 2
    assert counts.attention_flops_fwd(MISTRAL, 2048) == fwd == 34_359_738_368
    want = (4 * counts.frozen_matmul_params(MISTRAL)
            + 6 * 41_943_040 + 3 * fwd * 32 / 2048)
    assert counts.lora_train_flops_per_token(MISTRAL, 2048) == pytest.approx(want)
    # and it is about two thirds of the 6N the old bench credited
    six_n = 6 * (counts.frozen_matmul_params(MISTRAL) + 4096 * 32768)
    assert 0.6 < want / six_n < 0.75


@pytest.mark.parametrize("kind,matmuls", [("fwd", 2), ("bwd_dq", 3), ("bwd_dkv", 4)])
def test_flash_call_flops(kind, matmuls):
    one = 2 * 2048 * 2048 * 32 * 128 / 2   # one causal matmul, one sequence
    assert counts.flash_call_flops(MISTRAL, 8, 2048, kind) == matmuls * one * 8


def test_kv_bytes_and_paged_call():
    assert counts.kv_bytes_per_token(MISTRAL) == 2 * 8 * 128 * 2 == 4096
    assert 32 * counts.kv_bytes_per_token(MISTRAL) == 131072   # all layers
    assert counts.paged_decode_call_bytes(MISTRAL, 1000) == 4_096_000
    assert counts.paged_decode_call_flops(MISTRAL, 1000) == 4 * 1000 * 32 * 128


def test_roofline_and_peaks():
    peaks = counts.peaks_for("TPU v5 lite")
    assert peaks["bf16_flops"] == 197e12 and peaks["hbm_bytes_per_s"] == 819e9
    t, bound = counts.roofline_seconds(197e12, 1.0, peaks)
    assert (t, bound) == (1.0, "compute")
    t, bound = counts.roofline_seconds(1.0, 819e9 * 2, peaks)
    assert (t, bound) == (2.0, "memory")
    with pytest.raises(KeyError):
        counts.peaks_for("cpu")


@pytest.mark.parametrize("p,want", [(50, 5), (90, 9), (95, 10), (100, 10), (1, 1)])
def test_percentile_is_nearest_rank(p, want):
    assert stats.percentile(list(range(10, 0, -1)), p) == want


def test_spread_is_interquartile_over_median():
    values = [100, 101, 102, 103, 104, 105]
    import statistics

    q = statistics.quantiles(values, n=4)
    assert stats.spread(values) == pytest.approx((q[2] - q[0]) / 102.5)
