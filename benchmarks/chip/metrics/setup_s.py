"""Seconds from process start to the first due request: weights, engine,
warm-up of every program the mix can ask for, a backlog's first wave."""


def read(ctx):
    return ctx.setup_s
