"""Share of their roofline that the held experts' grouped matrix
products reach in training: the least time of the three products over
the (token, expert) pairs the step routed to held experts, forward (again
where layers are recomputed) and backward (``expert_cost`` of the
configuration's reference), times the steps in the traced window, over
the device seconds of the ops under the ``moe_experts`` scope (the
products and the gathers that sort and unsort the pairs).  Cells whose
kind gives no such cost read nothing."""

from harness.counts import roofline_s


def read(ctx):
    if ctx["kind"] != "train" or "expert_cost" not in ctx:
        return None
    spent = ctx["sub_scope_s"]["moe_experts"]
    if spent <= 0:
        return None
    least = roofline_s(*ctx["expert_cost"], ctx["peak"])[0]
    return 100.0 * ctx["steps"] * least / spent
