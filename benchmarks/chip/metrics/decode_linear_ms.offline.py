"""Op self time in milliseconds per execution of the packed decode
(``_decode_fn``) in the scope ``linear`` (``phases.split``):
``quant/qlinear.py:linear_apply``, activation quantization included."""
import phases


def read(ctx):
    t = ctx.trace
    return phases.split(t, t).get("decode_linear_ms") \
        if t and "scope_s" in t else None
