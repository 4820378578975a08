"""round_us: device time per permute round of a step: the step program's
device time per run (its permutes, its other leaf operations and its
loops' own time, as ``all_reduce_roofline`` measures it) over the rounds
that the step's programs hold, ``rounds_per_step`` (each call's
``num_rounds`` times its count). Where messages are small this is the
per-round cost that fewer rounds and cheaper rounds move."""


def read(ctx):
    t, mod = ctx.trace, ctx.step_module
    runs = t.module_runs.get(mod) if t else None
    rounds = ctx.counters.get("rounds_per_step")
    if not runs or not rounds:
        return None
    measured = (t.permute_s[mod] + t.other_s[mod] + t.loop_s[mod]) / runs
    return measured / rounds * 1e6
