"""all_reduce_roofline: percent of the interconnect roofline that a step's
all-reduces reach. Least time: for each all-reduce, the bytes each chip
must send, 2(n-1)/n of its input, over the chip's ICI peak; it comes from
the work, not from PCCL's schedule. Measured time: the step program's
device time per step from the trace: its permutes, its other leaf
operations and its loops' own time."""

from chipbench.peaks import least_seconds


def read(ctx):
    t, mod = ctx.trace, ctx.step_module
    runs = t.module_runs.get(mod) if t else None
    if not runs:
        return None
    least = sum(count * least_seconds(kind, nbytes, n, ctx.device_kind)
                for kind, nbytes, n, count in ctx.work)
    measured = (t.permute_s[mod] + t.other_s[mod] + t.loop_s[mod]) / runs
    return 100.0 * least / measured
