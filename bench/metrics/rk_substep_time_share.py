"""Self device time of the RK substep (named scope `solver.rk_substep`: the
stage arithmetic, without the RHS layout work and kernel it calls) over the
traced window (bench/scopes.py)."""
from bench import scopes


def read(ctx):
    return scopes.share(ctx, "solver.rk_substep")
