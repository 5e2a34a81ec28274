"""Sharded-encode byte-identity self-check (DESIGN.md Sec. 6).

Acceptance gate for the scale-out encode path: for every mode x D regime
the sharded session (channel axis split over 2+ devices via shard_map) and
the request coalescer must emit streams whose decoded output -- and, for
the session, the exact segment bytes -- match the single-device encode.

Run in a subprocess so the forced host device count precedes the jax
import (the tier-1 test tests/test_shard_encode.py does exactly that):

  REPRO_SHARD_DEVICES=4 PYTHONPATH=src python -m repro.launch.shard_check

Prints one JSON record; "status": "ok" means every case was byte-identical.
"""
import os

if __name__ == "__main__":  # own the device-count flag (precedes jax import)
    _flag = ("--xla_force_host_platform_device_count="
             + os.environ.get("REPRO_SHARD_DEVICES", "2"))
    # append to any pre-existing XLA_FLAGS (last occurrence wins) so an
    # exported XLA_FLAGS cannot silently demote the check to 1 device
    os.environ["XLA_FLAGS"] = (
        (os.environ.get("XLA_FLAGS", "") + " " + _flag).strip())

import json
from typing import List, Tuple

import numpy as np

__all__ = ["run_check"]

CASES = [  # (mode, num_dict, value_range)
    ("std", 255, None),
    ("std", 1, None),
    ("residual", 32, (0.0, 360.0)),
    ("residual", 1, None),
    ("delta", 32, None),
    ("delta", 1, (0.0, 360.0)),
]


def _signal(n: int, vr, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    parts = [rng.normal(m, s, size=n // 3)
             for m, s in [(0, 1), (5, 0.5), (0, 1)]]
    x = np.concatenate(parts + [rng.normal(0, 1, size=n - 3 * (n // 3))])
    if vr is not None:
        x = np.mod(np.abs(x) * 40.0, vr[1] - vr[0]) + vr[0]
    return x


def _carry_devices(state) -> int:
    """Devices that hold a shard of an encode carry: what the plan really
    spread, not what its mesh promised."""
    import jax

    return len(set().union(*(leaf.sharding.device_set
                             for leaf in jax.tree.leaves(state))))


def _session_blobs(codec, chans, plan) -> Tuple[List[bytes], int]:
    """Per-channel stream bytes of a two-feed session, and the devices its
    carry spanned."""
    C = chans.shape[0]
    s = codec.session(channels=C, plan=plan)
    parts = [s.feed(chans[:, :517]), s.feed(chans[:, 517:])]
    spanned = _carry_devices(s._dev_state)
    parts.append(s.finish())
    return [b"".join(p[ci] for p in parts) for ci in range(C)], spanned


def run_check(backend: str = "jax", channels: int = 5,
              samples: int = 16 * 80 + 7, dict_shards: int = 0,
              cases=CASES, block_size: int = 16,
              alpha: float = 0.05) -> dict:
    """``dict_shards=0`` shards the dictionary over all devices (D-axis
    case) in addition to the channel-sharded cases; ``1`` disables it.
    ``cases`` are ``(mode, num_dict, value_range)``.  The record's
    ``spans`` lists, per checked path, how many devices its encode carry
    was spread over."""
    import jax

    from repro.core import IdealemCodec
    from repro.launch.encode_plan import make_encode_plan
    from repro.serve import FlushPolicy, StreamCoalescer

    n_dev = jax.device_count()
    want = int(os.environ.get("REPRO_SHARD_DEVICES", "0"))
    if want and n_dev != want:
        return {"status": "wrong_device_count", "devices": n_dev,
                "expected": want}
    if dict_shards == 0:
        dict_shards = n_dev
    B = block_size
    checked, spans = [], {}
    for mode, num_dict, vr in cases:
        case = f"{mode}/D{num_dict}"
        codec = IdealemCodec(mode=mode, block_size=B, num_dict=num_dict,
                             alpha=alpha, rel_tol=0.5, value_range=vr,
                             backend=backend)
        chans = np.stack([_signal(samples, vr, seed=11 + ci)
                          for ci in range(channels)])
        plan = make_encode_plan(channels, block_size=B)
        assert plan.num_devices == min(n_dev, channels), plan.summary()

        # sharded session bytes == single-device session bytes
        single, _ = _session_blobs(codec, chans, plan=None)
        sharded, spans[f"{case}/session"] = _session_blobs(codec, chans,
                                                           plan=plan)
        if single != sharded:
            return {"status": "mismatch", "where": "session",
                    "mode": mode, "num_dict": num_dict}

        # D-sharded session bytes == single-device session bytes: the
        # dictionary rows of every channel split over the dict mesh axis,
        # per-step best match all-reduced (one channel group: the fat-
        # channel scale-out the channel-sharded path cannot provide)
        if dict_shards > 1:
            dplan = make_encode_plan(channels, block_size=B,
                                     dict_shards=dict_shards)
            assert dplan.dict_shards == dict_shards, dplan.summary()
            dsharded, spans[f"{case}/session_dshard"] = _session_blobs(
                codec, chans, plan=dplan)
            if single != dsharded:
                return {"status": "mismatch", "where": "session_dshard",
                        "mode": mode, "num_dict": num_dict}

        # coalesced ragged streams decode like one-shot per-stream encode
        cplan = make_encode_plan(-(-channels // n_dev) * n_dev, block_size=B)
        co = StreamCoalescer(policy=FlushPolicy(max_batch_blocks=64),
                             plan=cplan, mode=mode, block_size=B,
                             num_dict=num_dict, alpha=alpha, rel_tol=0.5,
                             value_range=vr, backend=backend)
        segs = {ci: [] for ci in range(channels)}
        for ci in range(channels):
            co.open_stream(str(ci))
        step = [37 + 13 * ci for ci in range(channels)]
        lo = [0] * channels
        while any(lo[ci] < samples for ci in range(channels)):
            for ci in range(channels):
                if lo[ci] < samples:
                    res = co.submit(str(ci), chans[ci, lo[ci]:lo[ci] + step[ci]])
                    lo[ci] += step[ci]
                    if res:
                        for k, v in res.items():
                            segs[int(k)].append(v)
        spans[f"{case}/coalescer"] = _carry_devices(co._state)
        for ci in range(channels):
            segs[ci].append(co.close_stream(str(ci)))
        for ci in range(channels):
            got = codec.decode(b"".join(segs[ci]))
            ref = codec.decode(codec.encode(chans[ci]))
            if not np.array_equal(got, ref):
                return {"status": "mismatch", "where": "coalescer",
                        "mode": mode, "num_dict": num_dict, "channel": ci}
        checked.append(case)
    return {"status": "ok", "devices": n_dev, "backend": backend,
            "cases": checked, "spans": spans}


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="jax",
                    choices=["jax", "pallas"])
    ap.add_argument("--dict-shards", type=int, default=0,
                    help="dictionary shards for the D-axis case "
                         "(0 = all devices, 1 = skip)")
    args = ap.parse_args()
    rec = run_check(backend=args.backend, dict_shards=args.dict_shards)
    print(json.dumps(rec))
    if rec["status"] != "ok":
        raise SystemExit(1)


if __name__ == "__main__":
    main()
