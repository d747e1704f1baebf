"""Device time of operations under no named scope, the channel's fused
wall RHS kernel excepted, over the traced window (bench/scopes.py): what
the scope metrics do not cover in the channel cell."""
from bench import scopes


def read(ctx):
    return scopes.share(ctx, None)
