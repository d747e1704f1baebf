"""Self device time of the rollout (named scope `fleet.rollout`: bank draw,
action noise, scan plumbing, observation and reward, without the policy and
the solver it calls) over the traced window (bench/scopes.py)."""
from bench import scopes


def read(ctx):
    return scopes.share(ctx, "fleet.rollout")
