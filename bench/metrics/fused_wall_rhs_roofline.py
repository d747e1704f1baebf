"""The wall RHS kernel's share of its roofline: per call the larger of the
algorithmic operations over the bf16 peak and the minimal HBM bytes over
the HBM bandwidth (bench/counts/channel.py), times the calls, over the
kernel's device time — the reader of `fused_ns_rhs_roofline` on the
kernel and counts the channel driver hands it."""
from bench import common


def read(ctx):
    return common.load_reader("fused_ns_rhs_roofline")(ctx)
