"""Coarse route's code scan, share of its roofline: the least time the chip
could take to score the window's queries against the int8 codes
(work/code_scan.py) over the device time under the ``code_scan`` scope."""
from harness import coarse_program


def read(ctx):
    s = coarse_program.scope_seconds(ctx, "code_scan")
    c = ctx.counts
    if not s or not ctx.peaks:
        return None
    bound = ctx.work("code_scan").bound_s(c["nq"], c["live"], c["dim"],
                                          ctx.peaks)
    return 100.0 * bound * c["requests"] / s
