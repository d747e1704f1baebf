"""Device time of operations under no named scope, the fused RHS kernel
excepted, over the traced window (bench/scopes.py): what the scope metrics
do not cover."""
from bench import scopes


def read(ctx):
    return scopes.share(ctx, None)
