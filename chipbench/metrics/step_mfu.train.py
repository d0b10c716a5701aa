"""The whole train step's share of the chip's peak: the least time the
model's operations for the tokens trained in the traced window could take,
each at the peak of the precision the cell's policy runs it in (the
blocks' matrices at ``linears_precision``, the head and attention at
bfloat16), over the traced window, in percent."""
from chipbench import flops


def read(ctx):
    if ctx.mix["kind"] != "train" or not ctx.red.chips:
        return None
    cfg = ctx.res["cell"].cfg
    per_token = flops.train_least_time_per_token(
        cfg, ctx.mix["seq"], ctx.device_kind, ctx.mix["linears_precision"])
    return 100.0 * per_token * ctx.res["window"]["tokens"] / ctx.red.window_s
