"""Trace reduction on a small hand-made trace (nanoseconds)."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchkit import xplane  # noqa: E402
from benchkit.xplane import Event, Trace  # noqa: E402


def small_trace():
    # window [1000, 2000); device busy 1100-1300 (two overlapping ops in
    # one module), 1500-1600, and a module straddling the window's end
    modules = [Event("jit_scan(1)", 1100, 1300),
               Event("jit_fn(2)", 1500, 1600),
               Event("jit_scan(1)", 1950, 2100),
               Event("jit_scan(1)", 500, 900)]       # before the window
    ops = [Event("encode_step", 1100, 1250), Event("sort", 1200, 1300),
           Event("gather", 1500, 1600), Event("encode_step", 1950, 2100)]
    host = [Event(xplane.WINDOW_SPAN, 1000, 2000),
            Event("bench:Tenant.feed", 1250, 1480),
            Event("bench:IdealemSession.commit", 1320, 1450),
            Event("bench:ServeFrontend.tick", 1700, 1800)]
    return Trace(devices={"/device:TPU:0": {"modules": modules,
                                            "ops": ops}}, host=host)


def test_window_and_busy():
    tr = small_trace()
    assert xplane.window(tr) == (1000, 2000)
    assert xplane.window_seconds(tr) == pytest.approx(1000e-9)
    # 200 + 100 + 50 (clipped at the window's end)
    assert xplane.busy_seconds(tr) == pytest.approx(350e-9)
    assert xplane.idle_share(tr) == pytest.approx(65.0)


def test_union_merges_overlaps():
    assert xplane.union_length([(0, 10), (5, 15), (20, 25)]) == 20
    assert xplane.union_length([]) == 0


def test_program_time_and_calls_by_module_name():
    tr = small_trace()
    assert xplane.program_time(tr, "jit_scan") == pytest.approx(250e-9)
    assert xplane.program_calls(tr, "jit_scan") == 2
    assert xplane.program_calls(tr, "jit_fn") == 1
    assert xplane.program_calls(tr, "jit_none") == 0


def test_top_ops_sums_clipped_time():
    tr = small_trace()
    top = dict((n, s) for n, s in xplane.top_ops(tr))
    assert top["encode_step"] == pytest.approx(200e-9)   # 150 + 50
    assert top["gather"] == pytest.approx(100e-9)
    assert list(top)[0] == "encode_step"


def test_idle_gaps_labelled_by_innermost_host_span():
    tr = small_trace()
    gaps = xplane.idle_gaps(tr)
    # gaps: 1000-1100, 1300-1500, 1600-1950; longest first
    assert [g[1] for g in gaps] == pytest.approx([350e-9, 200e-9,
                                                  100e-9])
    assert gaps[0][0] == "bench:ServeFrontend.tick"     # middle 1775
    assert gaps[1][0] == "bench:IdealemSession.commit"  # middle 1400
    assert gaps[2][0] == "no host span"                 # middle 1050


def test_idle_share_without_device_is_none():
    tr = Trace(devices={}, host=[Event(xplane.WINDOW_SPAN, 0, 10)])
    assert xplane.idle_share(tr) is None
    assert xplane.idle_gaps(tr) == []


def test_missing_window_span_raises():
    with pytest.raises(ValueError):
        xplane.window(Trace(devices={}, host=[]))


def test_ops_line_stands_in_for_missing_modules():
    tr = Trace(devices={"/device:TPU:0": {"ops": [Event("a", 0, 40)]}},
               host=[Event(xplane.WINDOW_SPAN, 0, 100)])
    assert xplane.idle_share(tr) == pytest.approx(60.0)
