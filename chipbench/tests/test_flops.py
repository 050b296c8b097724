"""Peaks and the operation and byte counts, against numbers worked by hand."""

import json

import pytest

from chipbench import flops

SMOLLM = json.loads((flops.PEAKS_FILE.parent / "configs" / "smollm-360m.json").read_text())


def test_unknown_device_kind_is_an_error():
    with pytest.raises(flops.UnknownDevice, match="cpu"):
        flops.peaks("cpu")


def test_v5e_peaks_from_their_source():
    p = flops.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["ici_bytes_per_s"] == 1600e9 / 8
    assert "TPU v5e" in json.loads(flops.PEAKS_FILE.read_text())["source"]


def test_smollm_model_flops():
    # per layer: q 960x960, k and v 960x320, o 960x960, MLP 3 x 960x2560
    per_layer = 960 * 960 * 2 + 960 * 320 * 2 + 3 * 960 * 2560
    n = 32 * per_layer + 960 * 49152          # + the tied unembedding
    assert flops.matmul_params(SMOLLM) == n
    attn = 12 * 32 * 960 * 2048
    assert flops.train_flops_per_token(SMOLLM, 2048) == pytest.approx(6 * n + attn)
    assert flops.train_flops_per_token(SMOLLM, 2048) == pytest.approx(2.926e9, rel=1e-3)
    unembed = 960 * 49152                     # logits at the last position only
    assert flops.prefill_flops(SMOLLM, 8, 256) == pytest.approx(
        8 * (256 * (2 * (n - unembed) + 4 * 32 * 960 * 256) + 2 * unembed))
    assert flops.decode_flops(SMOLLM, 4, 300) == pytest.approx(
        4 * (2 * n + 4 * 32 * 960 * 300))


def test_allreduce_bytes_follow_nccl_tests():
    size, n = 64 * 2**20, 4
    assert flops.allreduce_wire_bytes(size, n) == 1.5 * size
    assert flops.allreduce_hbm_bytes(size) == 2 * size
    # 64 MiB in 4 ms across 4 ranks: algbw 16.8 GB/s, busbw 1.5 times that
    assert flops.busbw(size, 4e-3, n) == pytest.approx(size / 4e-3 * 1.5)
