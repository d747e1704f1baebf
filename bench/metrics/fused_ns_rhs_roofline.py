"""The fused RHS kernel's share of its roofline: per call the least time
the chip could take, the larger of the algorithmic operations over the
bf16 peak and the minimal HBM bytes over the HBM bandwidth
(bench/counts/hit.py), times the calls, over the kernel's device time.
At these sizes the bytes bound it."""


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    secs, count = tr.kernel(ctx["rhs_kernel"])
    if count == 0 or secs <= 0:
        return None
    peaks = ctx["peaks"]
    least = max(ctx["rhs_call_flops"] / peaks["bf16_flops_per_s"],
                ctx["rhs_call_bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least * count / secs
