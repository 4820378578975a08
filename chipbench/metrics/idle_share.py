"""idle_share: percent of the traced window in which a chip ran no
operation (mean over chips)."""


def read(ctx):
    t = ctx.trace
    if not t or not t.chips:
        return None
    return 100.0 * t.idle_share
