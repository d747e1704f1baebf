"""Device time of the broker (named scope `fleet.broker`: reading the latest
trajectory from the ring, slicing off padding and the donated ring writes)
over the traced window (bench/scopes.py)."""
from bench import scopes


def read(ctx):
    return scopes.share(ctx, "fleet.broker")
