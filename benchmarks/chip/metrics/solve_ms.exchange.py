"""Device time of the boundary exchange: the entry q/u shift and splice,
the p shift to p_next, and the overlap starts and finishes (scope
``admm.exchange``), in ms per iteration; the largest over the chips."""
from scopes import solve_ms


def read(ctx):
    return solve_ms(ctx, "exchange")
