"""The fused 8-bit AdamW kernel's share of its roofline in a training cell:
the bytes of its operands and results moved once at the HBM peak, summed
over its calls in the traced window, over their summed device time, in
percent.  Nothing to read where the fused kernel did not run."""
from chipbench import flops, peaks

KERNELS = ("_adamw_kernel",)


def read(ctx):
    calls = ctx.red.kernel_ops(KERNELS)
    if ctx.mix["kind"] != "train" or not calls:
        return None
    least = sum(flops.kernel_least_time(c, ctx.device_kind)
                for _, c in calls)
    spent = sum(o.end - o.start for o, _ in calls) / 1e9
    return peaks.share(least, spent)
