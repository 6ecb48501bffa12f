"""Share of the traced training window in which a core runs no op,
averaged over cores."""


def read(ctx):
    if ctx["kind"] != "train":
        return None
    return 100.0 * ctx["reduced"].idle_share
