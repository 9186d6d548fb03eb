"""Device-idle milliseconds a traced ``ServeEngine.step()`` spends with
``engine.launch`` as the innermost span (``phases.split``): page growth,
the slot arrays, their uploads and the decode dispatch."""
import phases


def read(ctx):
    t = ctx.trace
    return phases.split(t, t).get("host_launch_ms") \
        if t and "phase_idle_s" in t else None
