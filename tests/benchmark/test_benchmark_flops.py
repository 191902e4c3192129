"""The FLOP and byte functions against counts made by hand, for both
configurations and for the paged-attention kernel."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import flops, harness  # noqa: E402


def cfg_of(name):
    cell = harness.load_cell(name)
    return harness.flat_config(cell.config, rehearse=False)


def shape_of(cfg):
    return harness.load_task(cfg["task"]).flop_shape(cfg)


def test_lm_step_by_hand():
    cfg = cfg_of("lm_train")
    n, c, m, v = 1024, 512, 2048, 32000
    self_layer = 2 * n * c * c * 4 + 2 * 2 * n * n * c + 2 * 2 * n * c * c
    cross = 2 * n * c * c * 2 + 2 * 2 * n * m * c + 2 * 2 * n * c * c
    kv = 2 * 2 * m * c * c
    k = 0.15 * m
    decoder = (2 * k * c * c * 2 + 2 * 2 * n * c * c + 2 * 2 * k * n * c
               + 2 * 2 * k * c * c)
    out = 2 * k * c * v
    forward = 36 * self_layer + 3 * cross + 2 * kv + decoder + out
    assert sum(flops.forward_parts(cfg, shape_of(cfg)).values()) \
        == pytest.approx(forward)
    step = flops.train_step_flops(cfg, 24, shape_of(cfg))
    assert step == pytest.approx(3 * 24 * forward)
    # what XLA's cost analysis reads for the same step (scan bodies
    # counted once, PERF.md): 3.65e12
    assert 16e12 < step < 17e12


def test_image_step_by_hand():
    cfg = cfg_of("img_train")
    n, c, m, cin = 512, 512, 224 * 224, 3 + 2 * (2 * 64 + 1)
    shape = shape_of(cfg)
    assert (shape["positions"], shape["channels"]) == (m, cin)
    assert cin == 261
    self_layer = 12 * n * c * c + 4 * n * n * c
    cross_dense = 8 * n * c * c
    attend = 4 * n * m * c
    kv = 4 * m * cin * c
    decoder = 8 * 1 * c * c + 4 * n * c * c + 4 * 1 * n * c
    out = 2 * c * 1000
    rest = 36 * self_layer + 6 * (cross_dense + attend) + decoder + out
    # the pixels need no gradient: the kv projection's backward is one
    # product, not two
    assert flops.train_step_flops(cfg, 8, shape) == pytest.approx(
        8 * (3 * rest + 2 * 2 * kv))
    parts = flops.forward_parts(cfg, shape)
    share = (parts["encoder_kv_projection"] + parts["encoder_cross_attention"]
             + parts["encoder_cross_dense"]) / sum(parts.values())
    assert 0.78 < share < 0.86


def test_cross_attention_share_of_the_lm_is_small():
    cfg = cfg_of("lm_train")
    parts = flops.forward_parts(cfg, shape_of(cfg))
    share = (parts["encoder_kv_projection"] + parts["encoder_cross_attention"]
             + parts["encoder_cross_dense"]) / sum(parts.values())
    assert 0.08 < share < 0.16


def test_decode_token_by_hand():
    cfg = cfg_of("lm_decode")
    n, c, kv = 1024, 512, 400
    per_layer = 4 * n * kv * c + 8 * n * c * c
    selfs = 36 * (12 * n * c * c + 4 * n * n * c)
    head = 8 * c * c + 4 * n * c * c + 4 * n * c + 2 * c * 32000
    assert flops.decode_token_flops(cfg, kv) == pytest.approx(
        3 * per_layer + selfs + head)
    assert 0.19e12 < flops.decode_token_flops(cfg, kv) < 0.22e12


def test_paged_attention_by_hand():
    ops, moved = flops.paged_attention_cost(
        [100, 0, 37], queries=1024, heads=8, head_dim=64)
    # QK^T and PV: 2 * 2 * queries * kv * head_dim per head
    assert ops == 4 * 1024 * 64 * 8 * (100 + 37)
    # bf16: q read and out written (2 * queries), k and v read (2 * kv),
    # per head and head_dim, for the two live rows
    assert moved == 2 * 8 * 64 * (2 * 1024 * 2 + 2 * (100 + 37))


def test_roofline_says_which_bound_binds():
    peak = flops.peaks("TPU v5 lite")
    assert flops.roofline_seconds(197e12, 1.0, peak) == (
        pytest.approx(1.0), "compute")
    t, which = flops.roofline_seconds(1.0, 819e9 * 2, peak)
    assert (t, which) == (pytest.approx(2.0), "memory")


def test_peaks_table():
    p = flops.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9 and p["hbm_bytes"] == 16e9
    assert "v5e" in p["source"]
    with pytest.raises(KeyError, match="not in benchmarks/peaks.json"):
        flops.peaks("TPU v9 imaginary")
