"""permute_ms: device time per step in collective-permute operations of the
step's program, from the profiler trace (mean over chips)."""


def read(ctx):
    t, mod = ctx.trace, ctx.step_module
    runs = t.module_runs.get(mod) if t else None
    if not runs:
        return None
    return t.permute_s[mod] / runs * 1e3
