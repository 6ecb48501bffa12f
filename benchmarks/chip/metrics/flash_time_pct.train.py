"""Share of the device's busy time that the flash kernels take in
training, from the traced window (all cores)."""


def read(ctx):
    red = ctx["reduced"]
    if ctx["kind"] != "train" or not red.flash:
        return None
    return 100.0 * sum(s for _, s in red.flash.values()) / (red.busy_s * red.cores)
