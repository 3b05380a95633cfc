"""Device time of the hidden z-update and the last-layer select (scope
``admm.z``), in ms per iteration; the largest over the chips."""
from scopes import solve_ms


def read(ctx):
    return solve_ms(ctx, "z")
