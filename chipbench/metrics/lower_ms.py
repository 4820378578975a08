"""lower_ms: mean host time per plan request inside
``to_ppermute_program`` and ``plan_buffers``, timed around the calls in the
traced run."""


def read(ctx):
    total, calls = ctx.spans.get("lowering", (0.0, 0))
    n = ctx.counters.get("requests")
    if not calls or not n:
        return None
    return total / n * 1e3
