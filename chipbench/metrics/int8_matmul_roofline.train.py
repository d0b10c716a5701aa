"""The int8 matmul kernels' share of their roofline in a training cell:
for every call of the forward, input-gradient and weight-gradient kernels
in the traced window, the least time its operations (at the int8 peak) or
its bytes (at the HBM peak) need; summed, over the summed device time of
those calls, in percent.  Nothing to read where no such kernel ran."""
from chipbench import flops, peaks

KERNELS = ("_int8_matmul_kernel", "_int8_matmul_nt_kernel",
           "_int8_matmul_tn_kernel")


def read(ctx):
    calls = ctx.red.kernel_ops(KERNELS)
    if ctx.mix["kind"] != "train" or not calls:
        return None
    least = sum(flops.kernel_least_time(c, ctx.device_kind)
                for _, c in calls)
    spent = sum(o.end - o.start for o, _ in calls) / 1e9
    return peaks.share(least, spent)
