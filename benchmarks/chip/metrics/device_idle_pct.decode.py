"""Share of the traced decode window in which the core runs no op."""


def read(ctx):
    if ctx["kind"] != "decode":
        return None
    return 100.0 * ctx["reduced"].idle_share
