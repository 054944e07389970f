"""The yardstick's counts from shapes, against counts made by hand."""
import json
import pathlib

import pytest

from chipbench import counts

CONFIGS = pathlib.Path(counts.__file__).resolve().parent / "configs"


def _config(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


# hand counts, per layer: q + k + v + o projections, then the MLP; the head
# is hidden x padded vocabulary; attention is 12 L H Q T (PaLM, app. B)
HAND = {
    # 14 x (1536*1536*2 + 1536*256*2 + 3*1536*8960) + 1536*76032
    "qwen2-1.5b": (14 * (4_718_592 + 786_432 + 41_287_680) + 116_785_152,
                   12 * 14 * 12 * 128 * 2048),
    # 2 x (4608*4608*2 + 4608*512*2 + 2*4608*18432) + 4608*6144
    "starcoder2-7b": (2 * (42_467_328 + 4_718_592 + 169_869_312) + 28_311_552,
                      12 * 2 * 36 * 128 * 2048),
}


@pytest.mark.parametrize("name", sorted(HAND))
def test_model_flops_per_token_match_hand_count(name):
    n, attn = HAND[name]
    assert counts.matmul_params(_config(name)) == n
    assert counts.model_flops_per_token(_config(name), 2048) == 6 * n + attn


def test_qwen_hand_totals():
    # 771,883,008 weights an activation multiplies; 5.16 GFLOP a token
    assert counts.matmul_params(_config("qwen2-1.5b")) == 771_883_008
    assert counts.model_flops_per_token(_config("qwen2-1.5b"),
                                        2048) == 5_159_780_352


def test_padded_vocab():
    assert counts.padded_vocab(75_968) == 76_032
    assert counts.padded_vocab(6_144) == 6_144


def test_compress_bytes_counts_only_routed_leaves():
    # 16 B (read e and delta, write g and e') per element of each leaf of
    # at least the routing floor; smaller leaves stay off the kernels
    assert counts.compress_bytes([100_000, 99_999, 3, 250_000],
                                 100_000) == 16 * 350_000
    assert counts.compress_bytes([5, 7], 100_000) == 0


def test_peaks_know_v5e():
    p = counts.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "source" in p


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5", ""])
def test_peaks_refuse_unknown_device_kind(kind):
    with pytest.raises(KeyError, match="no peaks"):
        counts.peaks(kind)
