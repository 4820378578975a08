"""plan_self_ms: mean host time per plan request in ``synthesize_program``
outside synthesis, validation, translation and buffer planning: the self
time of the program's ``pccl.plan`` span (request and cache keys, the
program and plan caches), from the spans recorded in the traced window."""

from chipbench.scopes import request_times


def read(ctx):
    rec, n = getattr(ctx, "recorder", None), ctx.counters.get("requests")
    if rec is None or not rec.spans or not n:
        return None
    return request_times(rec, n)["plan_self_ms"]
