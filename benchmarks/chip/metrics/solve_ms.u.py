"""Device time of the dual update (scope ``admm.u``), in ms per iteration; the
largest over the chips."""
from scopes import solve_ms


def read(ctx):
    return solve_ms(ctx, "u")
