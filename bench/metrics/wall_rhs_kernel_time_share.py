"""Device time of the wall-modelled channel's fused RHS kernel
(`fused_wall_rhs`) over the traced window: the reader of
`rhs_kernel_time_share`, on the kernel the channel driver names."""
from bench import common


def read(ctx):
    return common.load_reader("rhs_kernel_time_share")(ctx)
