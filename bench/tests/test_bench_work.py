import json
from pathlib import Path

import numpy as np
import pytest
import reference
import work

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
PEAK = work.peaks("TPU v5 lite")


def _dims(name):
    return reference.dims(json.loads((CONFIGS / f"{name}.json").read_text()))


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")


def test_v5e_peaks():
    assert PEAK == {"flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_granite_decode_flops_by_hand():
    d = _dims("granite-moe-3b-a800m")
    D, L = 1536, 32
    proj = 2 * D * (24 * 64 + 2 * 8 * 64) + 2 * 24 * 64 * D
    dense = proj + 2 * D * 40 + 8 * 6 * D * 512
    want = L * (dense + 4 * 24 * 64 * 100) + 2 * D * 49155
    assert work.decode_flops(d, 100) == want


def test_prefill_is_the_sum_of_its_tokens_less_the_heads():
    d = _dims("granite-moe-3b-a800m")
    P = 37
    head = 2 * 1536 * 49155
    tokens = sum(work.decode_flops(d, c) - head for c in range(1, P + 1))
    assert work.prefill_flops(d, P) == pytest.approx(tokens + head, rel=1e-12)


def test_expert_ffn_least_time_counts_useful_work():
    d = _dims("granite-moe-3b-a800m")
    D, F = 1536, 512
    counts = np.zeros((2, 40))
    counts[0, 3] = 5  # one layer call: 5 tokens on expert 3
    flops = 5 * 6 * D * F
    byts = 3 * D * F * 2 + 5 * 2 * 2 * D * 2  # expert_tp 2: two row reads
    want = max(flops / 197e12, byts / 819e9) + 0.0  # the idle call is free
    assert work.expert_ffn_least_s(d, counts, PEAK) == pytest.approx(want)
    assert work.expert_ffn_bound(d, counts, PEAK) == "bytes"


def test_expert_ffn_compute_bound_when_many_tokens():
    d = _dims("granite-moe-3b-a800m")
    counts = np.full((1, 40), 4096.0)
    assert work.expert_ffn_bound(d, counts, PEAK) == "flops"
    D, F = 1536, 512
    want = 40 * 4096 * 6 * D * F / 197e12
    assert work.expert_ffn_least_s(d, counts, PEAK) == pytest.approx(want)
