"""Mean host time of one ControllerService.flush() in the window (the
benchmark's serve.flush spans: total over count)."""


def read(ctx):
    total, count = ctx["spans"].total("serve.flush")
    if count == 0:
        return None
    return 1e3 * total / count
