"""Whole table call's share of the chip's peak: the forward jet's matmul
operations (``bench/work``) times the calls of the traced window, over its
host-clock length and the peak."""


def read(ctx):
    if not ctx.get("calls"):
        return None
    rate = ctx["flops_per_call"] * ctx["calls"] / ctx["window_s"]
    return 100.0 * rate / ctx["peaks"]["flops_per_s"]
