"""Requests per compiled serving batch over the window, from the
service's on-device counters (ControllerService.stats())."""


def read(ctx):
    if not ctx.get("batches"):
        return None
    return ctx["requests"] / ctx["batches"]
