"""Whole training step's share of the chip's peak: the step's model matmul
operations (forward and backward, ``bench/work``) times the steps of the
traced window, over its host-clock length and the peak."""


def read(ctx):
    if not ctx.get("steps"):
        return None
    rate = ctx["flops_per_step"] * ctx["steps"] / ctx["window_s"]
    return 100.0 * rate / ctx["peaks"]["flops_per_s"]
