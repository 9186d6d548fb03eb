"""Share of the traced window in which no operation ran on the device."""


def read(ctx):
    t = ctx.trace
    return (1 - t["busy_s"] / t["window_s"]) * 100 if t else None
