"""Share of the traced window of a training cell in which no operation ran
on the device: 1 - (union of device operations / window), in percent."""


def read(ctx):
    if ctx.mix["kind"] != "train" or not ctx.red.chips:
        return None
    return 100.0 * ctx.red.idle_share()
