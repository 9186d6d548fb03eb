"""Model operations of the traced window (prefill of the prompts admitted
in it, every decoded token with its attention over live context;
``ctx.counts``, the reference's) over the traced window's length times
the int8 peak."""


def read(ctx):
    if not ctx.trace:
        return None
    ops = sum(ctx.counts.prefill_ops(ctx.dims, n) for s in ctx.steps
              for n in s.prompts)
    ops += sum(ctx.counts.decode_step_ops(ctx.dims, s.decode_rows,
                                          s.live_positions)
               for s in ctx.steps)
    return ops / (ctx.trace["window_s"] * ctx.peak["int8_ops"]) * 100 \
        if ops else None
