"""Device time of the metrics: the psums, the risk and the Lagrangian (scope
``admm.metrics``), in ms per iteration; the largest over the chips."""
from scopes import solve_ms


def read(ctx):
    return solve_ms(ctx, "metrics")
