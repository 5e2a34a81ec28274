"""The benchmark's plain reference against the program, on the CPU: the
same stream bytes as a numpy-backend session, the same samples as the
host range decoder, and a lower-precision control that differs."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from benchkit import fleet, reference  # noqa: E402

CFG = json.loads((BENCH / "configs" / "upmu-gateway.json").read_text())
SEED = 2**31 + 99


def program_stream(codec, x, chunk):
    from repro.core import IdealemCodec

    kw = dict(codec)
    if kw.get("value_range") is not None:
        kw["value_range"] = tuple(kw["value_range"])
    s = IdealemCodec(**kw, backend="numpy").session(dtype=np.float32)
    parts = [s.feed(x[i:i + chunk]) for i in range(0, len(x), chunk)]
    return b"".join(parts) + s.finish()


@pytest.mark.parametrize("channel", [0, 4, 7])
def test_stream_bytes_equal_the_program_session(channel):
    codec = CFG["codecs"][CFG["channels"][channel]["kind"]]
    x = fleet.channel_series(CFG, SEED, 2, channel, 7200 * 3 + 50)
    want = program_stream(codec, x, 7200)
    assert reference.encode_stream(codec, x, 7200) == want


def test_dictionary_overwrites_match():
    # a small dictionary and a wandering level: FIFO overwrites (0xFF)
    codec = {**CFG["codecs"]["magnitude"], "num_dict": 4}
    rng = np.random.default_rng(0)
    x = np.concatenate([rng.normal(m, 1.0, 640) for m in
                        (0, 40, 80, 120, 160, 0, 40)]).astype(np.float32)
    got = reference.encode_stream(codec, x, 1000)
    assert got == program_stream(codec, x, 1000)
    assert b"\xff" in got


@pytest.mark.parametrize("channel", [1, 9])
def test_range_decode_equals_the_program_decoder(channel):
    from repro.store import Container, decode_range, pack

    codec = CFG["codecs"][CFG["channels"][channel]["kind"]]
    x = fleet.channel_series(CFG, SEED, 1, channel, 7200 * 4)
    blob = pack([program_stream(codec, x, 7200)])
    store = Container(blob)
    dec = reference.RangeDecoder(codec, x, 7200, seed=77)
    assert dec.total_blocks == store.total_blocks(0)
    for start, stop in [(0, 5), (60, 140), (dec.total_blocks - 30,
                                           dec.total_blocks)]:
        want = decode_range(store, start, stop, channel=0, seed=77,
                            backend="numpy")
        got = dec.decode(start, stop)
        assert got.tobytes() == np.asarray(want, np.float32).tobytes()


def test_critical_distance_matches_the_program():
    from repro.core.ks import critical_distance

    for n in (32, 111):
        assert reference.critical_distance(0.01, n, n) == pytest.approx(
            critical_distance(0.01, n, n), rel=1e-12)


def test_lower_precision_control_differs():
    low = reference.lower_dtype("float32")
    codec = CFG["codecs"]["angle"]
    x = fleet.channel_series(CFG, SEED, 0, 6, 7200 * 2)
    assert reference.encode_stream(codec, x, 7200, work_dtype=low) \
        != reference.encode_stream(codec, x, 7200)


def test_reference_imports_nothing_of_the_program():
    import ast

    tree = ast.parse((BENCH / "benchkit" / "reference.py").read_text())
    names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names]
    names += [n.module or "" for n in ast.walk(tree)
              if isinstance(n, ast.ImportFrom)]
    assert names and not [m for m in names if m.split(".")[0] in
                          ("repro", "jax")]
