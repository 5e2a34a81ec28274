"""Share of the encode scan's roofline: the least time its work needs on
this chip, over the device time of the encode-scan programs in the traced
window.

The programs are the XLA modules named ``PROGRAM`` (the jitted ``scan``
of ``repro.core.encoder``).  The work of each call is counted from the
configuration's shapes by ``benchkit.roofline.encode_scan_bytes``: payload
in, decisions out, dictionary carry read and written once, averaged over
the channels of one feed.  With no published peak for the KS comparisons
the bound is bytes over HBM bandwidth."""
from benchkit import roofline, xplane

PROGRAM = "jit_scan"


def read(ctx):
    if ctx.trace is None:
        return None
    calls = xplane.program_calls(ctx.trace, PROGRAM)
    busy = xplane.program_time(ctx.trace, PROGRAM)
    if calls == 0 or busy <= 0:
        return None
    shapes = roofline.feed_calls(ctx.cfg, ctx.mix["samples_per_feed"])
    per_call = sum(roofline.encode_scan_bytes(*s) for s in shapes) \
        / len(shapes)
    least = calls * per_call / ctx.peaks["hbm_bytes_per_s"]
    ctx.note(f"encode scan: {calls} calls, {busy:.6f} s on the device, "
             f"{calls * per_call:.0f} bytes least traffic")
    return 100.0 * least / busy
