"""Device time of the W-update (scope ``admm.W``), in ms per iteration; the
largest over the chips."""
from scopes import solve_ms


def read(ctx):
    return solve_ms(ctx, "W")
