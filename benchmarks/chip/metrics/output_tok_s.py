"""Output tokens that became visible in the window, over its seconds
(from its start to the end of the last step begun inside it), the tokens
of requests the window cut included."""


def read(ctx):
    t0, t1 = ctx.window["t0"], ctx.window["end"]
    n = sum(t0 < x <= t1 for tr in ctx.tracks for x in tr.times)
    return n / (t1 - t0)
