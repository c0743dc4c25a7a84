"""Kernel work functions, model FLOPs and the table of peaks, checked by
hand arithmetic at a tiny shape."""

import pytest

from bench.lib import flops, harness
from bench.tests.tiny_cells import ROOT

M = dict(num_layers=2, d_model=8, num_heads=4, num_kv_heads=2, head_dim=2,
         d_ff=16, vocab_size=10, window_size=3)


def _kernel(name):
    return harness.load_module(ROOT / "bench" / "kernels" / f"{name}.py")


def test_paged_decode_work_counts_held_tokens():
    k = _kernel("paged_decode")
    f, b = k.work(M, [3, 5])
    # QK^T and PV: 2 products x 2 flops x H=4 heads x hd=2 per held key
    assert f == 4 * 4 * 2 * (3 + 5)
    # K and V of the held tokens: 2 x KV=2 x hd=2 x 2 bytes, + q and out
    assert b == 2 * 2 * 2 * 2 * (3 + 5) + 2 * (2 * 4 * 2 * 2)


def test_flash_prefill_work_is_causal_and_windowed():
    k = _kernel("flash_prefill")
    # T=5, window 3: keys per query 1,2,3,3,3 = 12
    assert flops.causal_keys(5, 3) == 12
    assert flops.causal_keys(2, 3) == 3
    f, b = k.work(M, 5)
    assert f == 4 * 4 * 2 * 12
    assert b == (2 * 4 + 2 * 2) * 2 * 5 * 2


def test_least_time_takes_the_larger_bound():
    k = _kernel("paged_decode")
    peaks = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert k.least_time(100.0, 5.0, peaks) == 1.0
    assert k.least_time(10.0, 50.0, peaks) == 5.0


def test_model_flops_by_hand():
    w = 2 * 8 * 4 * 2 + 2 * 8 * 2 * 2 + 2 * 8 * 16  # wq+wo, wk+wv, mlp
    assert flops.layer_weights(M) == w
    per_tok = 2 * (2 * w) + 2 * 8 * 10
    assert flops.decode_step_flops(M, [4]) == per_tok + 2 * (4 * 4 * 2 * 4)
    assert flops.prefill_flops(M, 5) == (2 * (2 * w * 5 + 4 * 4 * 2 * 12)
                                         + 2 * 8 * 10)


def test_peaks_table_names_its_source_and_refuses_unknown_devices():
    p = harness.peaks_for(ROOT, "TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in harness.load_json(ROOT / "bench" / "peaks.json")[
        "source"]
    with pytest.raises(KeyError):
        harness.peaks_for(ROOT, "cpu")
