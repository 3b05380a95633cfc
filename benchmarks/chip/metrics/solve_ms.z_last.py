"""Device time of the z_L FISTA solve, ``_fista_last`` (scope
``admm.z_last``), in ms per iteration; the largest over the chips."""
from scopes import solve_ms


def read(ctx):
    return solve_ms(ctx, "z_last")
