"""Required operations and bytes against hand-worked figures; the table of
peaks."""

import json

import pytest

from benchmark import flops, harness


def _config(name):
    return json.loads((harness.HERE / "configs" / f"{name}.json").read_text())


def test_gpt2_medium_trained_token():
    cfg = _config("gpt2-medium")
    assert flops.n_blocks(cfg) == 12 * 24 * 1024 ** 2 == 301_989_888
    assert flops.n_head(cfg) == 50257 * 1024 == 51_463_168
    per_token = flops.train_flops_per_token(cfg, 1024)
    assert per_token == 6 * (301_989_888 + 51_463_168) + 6 * 24 * 1024 * 1024
    assert per_token == pytest.approx(2.272e9, rel=1e-3)
    # PR 22's 41,265 tokens/s is 47.6% of the v5e's 197 TFLOP/s by this count
    assert 41_265 * per_token / 197e12 == pytest.approx(0.476, abs=1e-3)
    assert 45_180 * per_token / 197e12 == pytest.approx(0.521, abs=1e-3)


def test_gpt2_xl_sizes():
    cfg = _config("gpt2-xl")
    assert flops.n_blocks(cfg) == 12 * 48 * 1600 ** 2 == 1_474_560_000
    assert flops.n_head(cfg) == 50257 * 1600 == 80_411_200


def test_a_served_request_by_hand():
    cfg = _config("gpt2-medium")
    n_blk, n_head, le = 301_989_888, 51_463_168, 24 * 1024
    # prompt of 3, two output tokens: inputs at positions 0..3, two logits
    whole = flops.serve_flops(cfg, 3, 0, 2)
    assert whole == 4 * 2 * n_blk + 4 * le * (0 + 1 + 2 + 3) + 2 * 2 * n_head
    # the second token alone: one input at position 3, one logit
    assert flops.serve_flops(cfg, 3, 1, 2) == 2 * n_blk + 4 * le * 3 + 2 * n_head
    assert flops.serve_flops(cfg, 3, 0, 1) + flops.serve_flops(cfg, 3, 1, 2) == whole
    assert flops.serve_flops(cfg, 3, 2, 2) == 0.0      # nothing delivered


def test_flash_call_and_its_bound():
    call = flops.flash_causal_call(8, 16, 1024, 64)
    assert call["flops"] == 7 * 8 * 16 * 1024 * 1024 * 64
    assert call["bytes"] == 12 * 8 * 16 * 1024 * 64 * 2
    least = flops.roofline_seconds(call, flops.peaks("TPU v5 lite"))
    assert least["bound"] == "compute"
    assert least["seconds"] == pytest.approx(call["flops"] / 197e12)


def test_peaks_table():
    v5e = flops.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["hbm_bytes"] == 16e9
    assert v5e["ici_bytes_per_s"] == 200e9          # 1,600 Gbit/s, not 400 GB/s
    assert "1,600 Gbit/s" in v5e["source"]
    with pytest.raises(KeyError, match="no peaks for device kind"):
        flops.peaks("cpu")
