"""Env-steps whose advance the blow-up guard reverted, over the env-steps
of the iterations completed in the window, in percent: the program's
`<scenario>/reverted_envs` counter of each window iteration's metrics
record.  A program without the counter gives nothing."""


def read(ctx):
    steps = ctx.get("window_env_steps")
    if not steps or ctx.get("reverted_env_steps") is None:
        return None
    return 100.0 * ctx["reverted_env_steps"] / steps
