"""Share of the packed decode program's roofline: for each traced decode
step, the least time the chip needs for it (the larger of the bytes the
work needs over peak HBM bandwidth and its operations over the int8
peak; ``ctx.counts``, the reference's), summed, over the device time of
``_decode_fn``."""


def read(ctx):
    p = ctx.trace and ctx.trace["programs"].get("_decode_fn")
    if not p or not p[0]:
        return None
    least = 0.0
    for s in ctx.steps:
        if s.decode_rows:
            b = ctx.counts.decode_step_bytes(ctx.dims, ctx.bits,
                                             s.decode_rows, s.live_positions)
            o = ctx.counts.decode_step_ops(ctx.dims, s.decode_rows,
                                           s.live_positions)
            least += max(b / ctx.peak["hbm_bytes_per_s"],
                         o / ctx.peak["int8_ops"])
    return least / p[0] * 100 if least else None
