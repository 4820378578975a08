"""copy_ms: device time per step in the step program's leaf operations
other than collective-permutes (slot updates, slices, receive-adds), from
the profiler trace (mean over chips). Time inside a loop op while no leaf
op runs is not counted here."""


def read(ctx):
    t, mod = ctx.trace, ctx.step_module
    runs = t.module_runs.get(mod) if t else None
    if not runs:
        return None
    return t.other_s[mod] / runs * 1e3
