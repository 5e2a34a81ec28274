"""Roofline counts against hand-worked shapes, and the peaks table."""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from benchkit import roofline  # noqa: E402


def test_encode_scan_bytes_std_feed():
    # 225 blocks of n=32 f32 in: 28,800 B; decisions 225 x (1+4+1) =
    # 1,350 B; carry 255x32x4 + 2x255x4 + 255 + 4 = 34,939 B, read and
    # written once
    assert roofline.encode_scan_bytes(225, 32, 255) == 28800 + 1350 \
        + 2 * 34939


def test_encode_scan_bytes_delta_feed_and_channels():
    carry = 255 * 111 * 4 + 2 * 255 * 4 + 255 + 4
    one = 64 * (111 * 4 + 6) + 2 * carry
    assert roofline.encode_scan_bytes(64, 111, 255) == one
    assert roofline.encode_scan_bytes(64, 111, 255, channels=3) == 3 * one


def test_ks_compares():
    assert roofline.encode_scan_ks_compares(225, 32, 255) == \
        225 * 255 * 32 * 32


def test_feed_calls_follow_the_configuration():
    cfg = json.loads((BENCH / "configs" / "upmu-gateway.json").read_text())
    calls = roofline.feed_calls(cfg, 7200)
    assert calls[:6] == [(225.0, 32, 255)] * 6
    assert calls[6:] == [(7200 / 112, 111, 255)] * 6


def test_peaks_table():
    p = roofline.load_peaks(BENCH / "peaks.json", "TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes"] == 16e9


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        roofline.load_peaks(BENCH / "peaks.json", "TPU v9 imaginary")
