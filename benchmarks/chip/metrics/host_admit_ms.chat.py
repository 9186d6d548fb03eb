"""Device-idle milliseconds a traced ``ServeEngine.step()`` spends with
``engine.admit`` as the innermost span (``phases.split``): reservations,
the prefix trie, the prefill arrays and their dispatch."""
import phases


def read(ctx):
    t = ctx.trace
    return phases.split(t, t).get("host_admit_ms") \
        if t and "phase_idle_s" in t else None
