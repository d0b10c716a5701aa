"""The whole train step's share of the chip's peak: the least time the
model's operations for the tokens trained in the traced window could take,
each at the peak of the precision the cell's policy runs it in (the
blocks' matrices at ``linears_precision``, everything else at bfloat16),
over the traced window, in percent.  The operations are counted by the
configuration's own reference (``chipbench.flops``)."""
from chipbench import flops


def read(ctx):
    if ctx.mix["kind"] != "train" or not ctx.red.chips:
        return None
    per_token = flops.train_least_time_per_token(
        ctx.conf, ctx.mix["seq"], ctx.device_kind,
        ctx.mix["linears_precision"])
    return 100.0 * per_token * ctx.res["window"]["tokens"] / ctx.red.window_s
