"""Op self time in milliseconds per execution of the packed decode
(``_decode_fn``) outside the model's scopes (``phases.split``):
embedding, logits, norms, ``quantize_kv`` and the pool's row writes."""
import phases


def read(ctx):
    t = ctx.trace
    return phases.split(t, t).get("decode_unscoped_ms") \
        if t and "scope_s" in t else None
