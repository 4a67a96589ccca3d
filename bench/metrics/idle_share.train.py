"""Share of the traced window, in %, in which no operation ran on the
chip, averaged over the chips of a training cell."""


def read(ctx):
    return 100.0 * ctx.trace.idle_share()
