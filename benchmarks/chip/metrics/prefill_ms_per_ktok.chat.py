"""Device milliseconds of the prefill programs (``_prefill_batched_fn``,
``_prefill_fn``) in the traced window, per 1000 prompt tokens of the
requests admitted in it (padding not counted)."""


def read(ctx):
    if not ctx.trace:
        return None
    progs = ctx.trace["programs"]
    dev = sum(progs[k][0] for k in ("_prefill_batched_fn", "_prefill_fn")
              if k in progs)
    ktok = sum(sum(s.prompts) for s in ctx.steps) / 1e3
    return dev / ktok * 1e3 if dev and ktok else None
