"""Device time of the p-update and its first-layer select (scope ``admm.p``),
in ms per iteration; the largest over the chips."""
from scopes import solve_ms


def read(ctx):
    return solve_ms(ctx, "p")
