"""Device time of the policy forward pass inside the rollout scan (named
scope `rollout.policy`) over the traced window (bench/scopes.py)."""
from bench import scopes


def read(ctx):
    return scopes.share(ctx, "rollout.policy")
