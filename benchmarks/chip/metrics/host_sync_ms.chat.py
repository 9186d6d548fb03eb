"""Device-idle milliseconds a traced ``ServeEngine.step()`` spends with
``engine.sync`` as the innermost span (``phases.split``): the argmax and
the host's read of the step's tokens (nested in ``engine.admit`` for
prefill)."""
import phases


def read(ctx):
    t = ctx.trace
    return phases.split(t, t).get("host_sync_ms") \
        if t and "phase_idle_s" in t else None
