"""``jet_flash_attention``'s share of its roofline in training: the least
time its calls of the traced window could take (``bench/work/
pinnsformer.py``) over the device time of its events."""

from bench import work


def read(ctx):
    trace = ctx.get("trace")
    if trace is None or not ctx.get("steps") \
            or not ctx.get("flash_calls_per_step"):
        return None
    seconds = trace.kernel_s("jet_flash_attention")
    if not seconds:
        return None
    least, _ = work.roofline_seconds(ctx["flash_calls_per_step"],
                                     ctx["peaks"]["flops_per_s"],
                                     ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least * ctx["steps"] / seconds
