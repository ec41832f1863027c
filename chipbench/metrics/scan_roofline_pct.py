"""Exact scan's share of its roofline: the least time the chip could take
to score the window's queries (work/scan.py) over the device time of the
scan ops of the search program."""
from harness import ops


def read(ctx):
    scan_s = ctx.view.seconds(ops.is_scan)
    c = ctx.counts
    if scan_s <= 0 or not ctx.peaks:
        return None
    bound = ctx.work("scan").bound_s(c["nq"], c["live"], c["dim"], ctx.peaks)
    return 100.0 * bound * c["requests"] / scan_s
