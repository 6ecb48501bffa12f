"""Share of their roofline that the flash kernels reach in training: the
least time of each call (the larger of its FLOPs over the peak and its
bytes over HBM bandwidth, ``harness.counts.flash_call``), summed over the
calls in the traced window, over the calls' summed device time.  At the
benchmark's shapes every call is compute-bound."""

from harness.counts import roofline_s


def read(ctx):
    flash = ctx["reduced"].flash
    if ctx["kind"] != "train" or not flash:
        return None
    least = sum(n * roofline_s(*ctx["flash_cost"][k], ctx["peak"])[0] for k, (n, _) in flash.items())
    spent = sum(s for _, s in flash.values())
    return 100.0 * least / spent
