"""Pieces shared by the traffic kinds (``bench/kinds/<kind>.py``), each of
which is the one general generator of its mixes' data files
(``bench/traffic/<mix>.json``).

For a steady benchmark a generator gives every seed the same multiset of
sizes and of arrival gaps: counts and quantiles are stratified, and only
their order and pairing change with the seed.
"""
from __future__ import annotations

import numpy as np

__all__ = ["rng", "zipf_counts", "channel_blocks", "arrivals"]


def rng(seed: int, stream: str) -> np.random.Generator:
    """A generator for one named stream of draws of a seed (any whole
    number, larger than 32 bits included)."""
    return np.random.default_rng(
        [seed % (1 << 64), int.from_bytes(stream.encode(), "little")])


def zipf_counts(n: int, items: int, s: float) -> np.ndarray:
    """Exactly ``n`` draws split over ``items`` by Zipf(s) weights
    (largest remainder)."""
    w = 1.0 / np.arange(1, items + 1) ** s
    share = n * w / w.sum()
    counts = np.floor(share).astype(np.int64)
    rest = n - counts.sum()
    counts[np.argsort(-(share - counts), kind="stable")[:rest]] += 1
    return counts


def channel_blocks(cfg: dict, channel: int, samples: int) -> int:
    """Whole blocks a channel's stream holds after ``samples`` samples."""
    kind = cfg["channels"][channel]["kind"]
    return samples // cfg["codecs"][kind]["block_size"]


def arrivals(rng: np.random.Generator, n: int, seconds: float) -> np.ndarray:
    """``n`` open-loop arrival times in ``[0, seconds)``, sorted: the gaps
    are the ``n`` quantiles of an exponential distribution (a Poisson
    process's gaps), scaled to sum to ``seconds`` and put in an order
    drawn from ``rng``.  Every seed gets the same gaps; only where the
    short and the long ones fall changes."""
    if n == 0:
        return np.zeros(0)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps *= seconds / gaps.sum()
    rng.shuffle(gaps)
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])])
