"""Device-idle milliseconds a traced ``ServeEngine.step()`` spends with
``engine.retire`` as the innermost span (``phases.split``): decoded
tokens appended, finished requests and their cells retired."""
import phases


def read(ctx):
    t = ctx.trace
    return phases.split(t, t).get("host_retire_ms") \
        if t and "phase_idle_s" in t else None
