"""Device time of the q-update (scope ``admm.q``), in ms per iteration; the
largest over the chips."""
from scopes import solve_ms


def read(ctx):
    return solve_ms(ctx, "q")
