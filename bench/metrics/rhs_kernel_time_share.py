"""Device time of the fused RHS kernel's events over the traced window."""


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or tr.window_s <= 0:
        return None
    secs, count = tr.kernel(ctx["rhs_kernel"])
    if count == 0:
        return None
    return 100.0 * secs / tr.window_s
