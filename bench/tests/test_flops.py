import dataclasses

import pytest

from bench.flops import prefill_flops, topk_least_bytes
from bench.peaks import peaks
from repro.configs import get_config


def test_verifier_prefill_at_sixteen_by_1048_on_fourteen_layers():
    cfg = dataclasses.replace(get_config("qwen2.5-vl-7b"), num_layers=14)
    f = prefill_flops(cfg, batch=16, seq=1048)
    assert f["matmul"] / 1e12 == pytest.approx(109.4, abs=0.1)
    assert f["attention"] / 1e12 == pytest.approx(3.53, abs=0.01)
    assert f["lm_head"] / 1e12 == pytest.approx(0.0174, abs=1e-4)
    assert f["total"] / 1e12 == pytest.approx(113.0, abs=0.1)
    assert f["total"] / 1e12 == pytest.approx(112.96, abs=0.005)


def test_topk_least_bytes_by_smallest_copy():
    assert topk_least_bytes(49240, 4096, "int4") == 49240 * 2048
    assert topk_least_bytes(10, 8, "int8") == 80
    assert topk_least_bytes(10, 8, "fp32") == 320


def test_peaks_known_kind_and_unknown_kind_is_an_error():
    p = peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert p["int8_ops"] == 393e12 and p["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")
