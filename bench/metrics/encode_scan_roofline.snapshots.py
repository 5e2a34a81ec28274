"""Share of the error-bounded encode scan's roofline: the least time its
work needs on this chip, over the device time of the encode-scan programs
in the traced window.

The programs are the XLA modules named ``PROGRAM`` (the jitted ``scan``
of ``repro.core.encoder``, one call per level line).  The work of a call
is counted from the configuration's shapes by
``benchkit.fields.scan_bytes``: payload in, decisions and the demotion
count out, and the carry -- sorted rows and the raw rows the bound is
checked against -- read and written once, for a feed's mean block count.
With no published peak for the KS comparisons the bound is bytes over HBM
bandwidth."""
from benchkit import fields, xplane

PROGRAM = "jit_scan"


def read(ctx):
    if ctx.trace is None:
        return None
    calls = xplane.program_calls(ctx.trace, PROGRAM)
    busy = xplane.program_time(ctx.trace, PROGRAM)
    if calls == 0 or busy <= 0:
        return None
    c = ctx.cfg["codec"]
    B = int(c["block_size"])
    nb = int(ctx.cfg["grid"]["y"]) * int(ctx.cfg["grid"]["x"]) / B
    per_call = fields.scan_bytes(nb, B - 1, int(c["num_dict"]))
    least = calls * per_call / ctx.peaks["hbm_bytes_per_s"]
    ctx.note(f"encode scan: {calls} calls, {busy:.6f} s on the device, "
             f"{calls * per_call:.0f} bytes least traffic")
    return 100.0 * least / busy
