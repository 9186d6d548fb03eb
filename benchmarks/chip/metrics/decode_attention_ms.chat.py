"""Op self time in milliseconds per execution of the packed decode
(``_decode_fn``) in the scope ``attention`` (``phases.split``):
``attend_cached``, ``attend_full``, ``attend_chunked`` and the Pallas
kernel."""
import phases


def read(ctx):
    t = ctx.trace
    return phases.split(t, t).get("decode_attention_ms") \
        if t and "scope_s" in t else None
