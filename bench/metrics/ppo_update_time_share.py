"""Device time of the PPO update (named scope `fleet.update`: policy forward
and backward over the trajectory, Adam, the non-finite guard) over the
traced window (bench/scopes.py)."""
from bench import scopes


def read(ctx):
    return scopes.share(ctx, "fleet.update")
