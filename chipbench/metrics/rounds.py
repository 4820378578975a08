"""rounds: ppermute rounds of the first call's program (the 25 MiB bucket's
all-reduce in the DDP cell): ``PpermuteProgram.num_rounds``, an exact count."""


def read(ctx):
    return ctx.counters.get("rounds")
