"""Peaks of the chip, and the work of the encode scan counted from its
shapes.

The encode scan (``repro.core.encoder``'s jitted ``scan``, running the
fused ``encode_step`` kernel once per block) takes ``nb`` payload rows of
width ``n`` per channel and a dictionary carry of ``D`` rows, and returns
one decision per block and the new carry.  Its least traffic with HBM is
counted for the whole call, however it is implemented: payload read once,
decisions written once, carry read once and written once.  One kernel per
block or one kernel per feed count the same work.

The ungated KS comparisons are ``D * n**2`` per block (every candidate
sample against every dictionary row); no VPU peak for them is published,
so the roofline share is bounded by bytes over HBM bandwidth alone.
"""
from __future__ import annotations

import json
from pathlib import Path

__all__ = ["load_peaks", "encode_scan_bytes", "encode_scan_ks_compares",
           "feed_calls"]

_DECISION_BYTES = 1 + 4 + 1   # is_hit (bool), slot (int32), overwrite (bool)


def load_peaks(path: Path, device_kind: str) -> dict:
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}; add the published figures")
    return table[device_kind]


def encode_scan_bytes(nb: float, n: int, D: int, itemsize: int = 4,
                      channels: int = 1) -> float:
    """Least HBM bytes of one scan call over ``nb`` blocks."""
    carry = D * n * itemsize + 2 * D * itemsize + D + 4
    return channels * (nb * (n * itemsize + _DECISION_BYTES) + 2 * carry)


def encode_scan_ks_compares(nb: float, n: int, D: int,
                            channels: int = 1) -> float:
    """Ungated KS comparisons of one scan call."""
    return channels * nb * D * n * n


def feed_calls(cfg: dict, samples_per_feed: int) -> list:
    """One ``(nb, n, D)`` per channel of a device for one feed of
    ``samples_per_feed`` samples, ``nb`` averaged over the feeds (a block
    that straddles two feeds is completed by the later one)."""
    out = []
    for ch in cfg["channels"]:
        c = cfg["codecs"][ch["kind"]]
        B = int(c["block_size"])
        n = B if c["mode"] == "std" else B - 1
        out.append((samples_per_feed / B, n, int(c["num_dict"])))
    return out
