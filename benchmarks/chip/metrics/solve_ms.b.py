"""Device time of the b-update (scope ``admm.b``), in ms per iteration; the
largest over the chips."""
from scopes import solve_ms


def read(ctx):
    return solve_ms(ctx, "b")
