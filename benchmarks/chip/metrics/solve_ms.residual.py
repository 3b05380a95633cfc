"""Device time of the entry residual r = z - pW - b (scope ``admm.residual``),
in ms per iteration; the largest over the chips."""
from scopes import solve_ms


def read(ctx):
    return solve_ms(ctx, "residual")
