"""The yardstick's operation and byte counts against hand values."""

from __future__ import annotations

import json
from pathlib import Path

import pytest

from perfbench import counts
from perfbench.peaks import PEAKS, peaks

ROOT = Path(__file__).resolve().parents[2]


def test_dgemm_flops():
    assert counts.dgemm_flops(8192, 8192, 8192) == 1_099_511_627_776
    assert counts.dgemm_flops(4096, 8192, 2048) == 2 * 4096 * 8192 * 2048


@pytest.mark.parametrize("n_bytes,length,moved", [
    (4 << 30, 357_913_941, 4_294_967_292),
    (256 << 20, 22_369_621, 268_435_452),
    (1000, 1024, 12_288),          # floor of 1024 elements
])
def test_triad_bytes(n_bytes, length, moved):
    assert counts.triad_length(n_bytes) == length
    assert counts.triad_bytes(n_bytes) == moved


def test_flash_flops():
    # granite-3-2b attention: batch 2, 32 heads, seq 2048, head dim 64
    assert counts.flash_flops(2, 32, 2048, 64, causal=False) \
        == 68_719_476_736
    assert counts.flash_flops(2, 32, 2048, 64) == 34_359_738_368
    # Q and O over 32 heads, K and V over 8, bf16: 40 MiB
    assert counts.flash_bytes(2, 32, 8, 2048, 64) == 41_943_040


def test_granite_train_step_flops():
    model = json.loads((ROOT / "perfbench/configs/granite_3_2b.json")
                       .read_text())["model"]
    assert counts.attention_matmul_params(model) == 10_485_760
    assert counts.mlp_matmul_params(model) == 50_331_648
    # 40 layers of 60,817,408 matmul params plus the 2048 x 49155 head
    # over 4096 tokens, causal attention, backward twice the forward
    assert counts.train_step_flops(model, 2, 2048) == 66_383_165_521_920


def test_peaks_are_keyed_by_device_kind():
    v5e = peaks("TPU v5 lite")
    assert v5e["flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="TPU v9"):
        peaks("TPU v9")
    assert set(PEAKS) == {"TPU v5 lite"}
