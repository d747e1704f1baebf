"""Share of the channel cell's traced window in which no operation ran on
the device (the reader of `device_idle_share.train`)."""
from bench import common


def read(ctx):
    return common.load_reader("device_idle_share.train")(ctx)
