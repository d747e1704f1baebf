"""Share of the traced serving window in which no operation ran on the
device (busy is the union of the device-op intervals, averaged over the
chips)."""


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or not tr.ops or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
