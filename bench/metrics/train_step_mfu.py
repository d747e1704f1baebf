"""The fleet program's share of the chips' bf16 peak: the algorithmic
operations of every whole iteration completed in the window
(bench/counts/hit.py) over the time to the last of them."""


def read(ctx):
    if not ctx.get("iterations") or ctx.get("elapsed_s", 0) <= 0:
        return None
    flops = ctx["iteration_flops"] * ctx["iterations"]
    peak = ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * flops / ctx["elapsed_s"] / peak
