"""validate_ms: mean host time per plan request in
``CollectiveAlgorithm.validate``: the program's ``pccl.validate`` span,
from the spans recorded in the traced window."""

from chipbench.scopes import request_times


def read(ctx):
    rec, n = getattr(ctx, "recorder", None), ctx.counters.get("requests")
    if rec is None or not rec.spans or not n:
        return None
    return request_times(rec, n)["validate_ms"]
