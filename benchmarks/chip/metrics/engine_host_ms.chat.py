"""Device-idle milliseconds inside each ``ServeEngine.step()`` span of
the traced window, averaged over its steps: host scheduling, uploads,
dispatch and the host's read of each step's tokens."""


def read(ctx):
    idle = ctx.trace and ctx.trace["step_idle_s"]
    return sum(idle) / len(idle) * 1e3 if idle else None
