"""Device time of the ops that no ``admm.*`` scope names (the scan's
bookkeeping and the copies the compiler adds), in ms per iteration; the
largest over the chips."""
from scopes import UNSCOPED, solve_ms


def read(ctx):
    return solve_ms(ctx, UNSCOPED)
