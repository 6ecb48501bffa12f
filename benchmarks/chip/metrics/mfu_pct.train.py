"""Model FLOP/s utilization of the training step, from the traced window:
model FLOPs per step (forward and backward matmuls, causal attention on
its unmasked half, recomputation not counted; ``harness.counts``) times
the steps in the window, over the window times chips times the peak."""


def read(ctx):
    if ctx["kind"] != "train":
        return None
    red = ctx["reduced"]
    flops = ctx["train_step_flops"] * ctx["steps"]
    return 100.0 * flops / (red.window_s * ctx["chips"] * ctx["peak"]["bf16_flops"])
