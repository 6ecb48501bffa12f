"""Roofline share of the whole decode step: for each step in the traced
window the least time (the larger of its FLOPs over the peak and its bytes
over HBM bandwidth: the weights once, the filled K/V read and the new K/V
written; ``harness.counts.decode_step``), summed, over the window."""


def read(ctx):
    if ctx["kind"] != "decode":
        return None
    return 100.0 * ctx["decode_roofline_s"] / ctx["reduced"].window_s
