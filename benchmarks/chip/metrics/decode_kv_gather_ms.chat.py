"""Op self time in milliseconds per execution of the packed decode
(``_decode_fn``) in the scope ``kv_gather`` (``phases.split``):
``attention._gather_pages``, the page gather and the gathered K/V's
relayout."""
import phases


def read(ctx):
    t = ctx.trace
    return phases.split(t, t).get("decode_kv_gather_ms") \
        if t and "scope_s" in t else None
