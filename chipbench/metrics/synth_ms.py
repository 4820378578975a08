"""synth_ms: mean host time per plan request inside
``SynthesisEngine.collective``, timed around the call in the traced run."""


def read(ctx):
    total, calls = ctx.spans.get("synthesis", (0.0, 0))
    n = ctx.counters.get("requests")
    if not calls or not n:
        return None
    return total / n * 1e3
