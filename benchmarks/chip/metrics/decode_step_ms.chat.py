"""Device milliseconds per execution of the packed decode program
(``_decode_fn``) in the traced window."""


def read(ctx):
    p = ctx.trace and ctx.trace["programs"].get("_decode_fn")
    return p[0] / p[1] * 1e3 if p and p[1] else None
