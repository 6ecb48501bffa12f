"""Share of the device's busy time that the expert layers take in
training: device seconds of the ops under the program's ``moe`` scope
(routing, the held experts and the shared expert, forward, recomputed
and backward; ``harness/scopes.py``) over the busy time of all cores.
Cells whose kind gives no seconds by scope read nothing."""


def read(ctx):
    if ctx["kind"] != "train" or "scope_s" not in ctx:
        return None
    red = ctx["reduced"]
    return 100.0 * ctx["scope_s"]["moe"] / (red.busy_s * red.cores)
