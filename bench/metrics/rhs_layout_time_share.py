"""Device time of the layout work around the fused RHS kernel (named scope
`rhs.layout`: reshape, pad and planar transposes before the kernel, the
transposes and slice after it) over the traced window (bench/scopes.py)."""
from bench import scopes


def read(ctx):
    return scopes.share(ctx, "rhs.layout")
