"""Roofline-term extraction from compiled XLA artifacts.

`cost_analysis()` supplies HLO FLOPs and HBM bytes; collective bytes are NOT
in cost_analysis, so `collective_bytes` parses the (post-SPMD, per-device)
optimized HLO text and sums, per collective family, the bytes each op moves.

Accounting convention (documented in EXPERIMENTS.md §Roofline): shapes in
the partitioned module are PER-DEVICE; for a ring implementation the bytes
crossing each device's link are ~the op's full (gathered/reduced) buffer:

    all-gather        output size            (each shard passes through)
    reduce-scatter    input  size (= sum of operand sizes)
    all-reduce        2x input size          (reduce-scatter + all-gather)
    all-to-all        input size
    collective-permute input size

The roofline terms (seconds, per step) then follow the brief's formulas with
per-device quantities: term = per_device_bytes / link_bw ==
global_bytes / (chips * link_bw).
"""
from __future__ import annotations

import dataclasses
import re

import numpy as np

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

# %name = dtype[d0,d1]{layout} op-name(...)
_DEF_RE = re.compile(
    r"%?([\w.\-]+)\s*=\s*\(?([a-z]\w*)\[([\d,]*)\][^ ]*\s+([\w\-]+)\(([^)]*)\)")
_SHAPE_RE = re.compile(r"([a-z]\w*)\[([\d,]*)\]")

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def _nbytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d.strip():
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


@dataclasses.dataclass
class CollectiveStats:
    bytes_by_kind: dict
    count_by_kind: dict

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_kind.values())


def collective_bytes(hlo_text: str) -> CollectiveStats:
    """Per-device collective traffic from optimized HLO text (see module
    docstring for the per-op convention)."""
    # name -> output bytes, for operand lookups
    sizes: dict[str, int] = {}
    for m in _DEF_RE.finditer(hlo_text):
        name, dtype, dims, op, _ = m.groups()
        sizes[name] = _nbytes(dtype, dims)

    bytes_by_kind: dict[str, int] = {k: 0 for k in _COLLECTIVES}
    count_by_kind: dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for m in _DEF_RE.finditer(hlo_text):
        name, dtype, dims, op, operands = m.groups()
        kind = next((k for k in _COLLECTIVES if op.startswith(k)), None)
        if kind is None:
            continue
        # multi-output collectives print a tuple result; fall back to
        # summing operand sizes when the regex saw '(' (bytes==0).
        out_bytes = _nbytes(dtype, dims)
        opnd_bytes = 0
        for ref in operands.split(","):
            ref = ref.strip().lstrip("%")
            ref = ref.split(" ")[-1].lstrip("%")
            opnd_bytes += sizes.get(ref, 0)
        if kind == "all-gather":
            moved = out_bytes or opnd_bytes
        elif kind == "all-reduce":
            moved = 2 * (opnd_bytes or out_bytes)
        elif kind == "reduce-scatter":
            moved = opnd_bytes or out_bytes
        else:  # all-to-all, collective-permute
            moved = opnd_bytes or out_bytes
        bytes_by_kind[kind] += moved
        count_by_kind[kind] += 1
    return CollectiveStats(bytes_by_kind, count_by_kind)


def remat_duplication(hlo_text: str) -> dict:
    """Crude remat/redundancy signal: dot-op count and fusion count."""
    return {
        "n_dot": len(re.findall(r"\bdot\(", hlo_text)),
        "n_fusion": len(re.findall(r"\bfusion\(", hlo_text)),
        "n_while": len(re.findall(r"\bwhile\(", hlo_text)),
    }


def roofline_terms(flops_per_dev: float, hbm_bytes_per_dev: float,
                   coll_bytes_per_dev: float, n_chips: int,
                   peak_flops: float, hbm_bw: float, link_bw: float,
                   fused_bytes_per_dev: float | None = None) -> dict:
    """The three roofline terms in seconds + the bottleneck label.

    Two memory figures are reported (EXPERIMENTS.md §Roofline):
      memory_raw_s   = cost_analysis "bytes accessed" / HBM_bw — the brief's
                       formula verbatim.  On the CPU backend this counts
                       every op's unfused operand+result I/O and overstates
                       fused-TPU HBM traffic by orders of magnitude.
      memory_s       = (arguments + outputs + 2*temporaries) / HBM_bw — a
                       fused-execution traffic estimate from the compiled
                       buffer assignment; used for bottleneck selection.
    """
    t_compute = flops_per_dev / peak_flops
    t_mem_raw = hbm_bytes_per_dev / hbm_bw
    t_memory = (fused_bytes_per_dev / hbm_bw
                if fused_bytes_per_dev is not None else t_mem_raw)
    t_coll = coll_bytes_per_dev / link_bw
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "memory_raw_s": t_mem_raw, "collective_s": t_coll}
    sel = {"compute_s": t_compute, "memory_s": t_memory,
           "collective_s": t_coll}
    bound = max(sel, key=sel.get)
    terms["bound"] = bound.replace("_s", "")
    # roofline fraction: useful-compute time over the max term (how close the
    # dominant term lets compute run at peak)
    t_max = max(sel.values())
    terms["roofline_fraction"] = float(t_compute / t_max) if t_max > 0 else 0.0
    return terms


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6*N*D (dense) / 6*N_active*D (MoE) for train;
    2*N*D for a forward-only cell (prefill), 2*N_active per token for decode.
    D = tokens processed in the cell."""
    n_params = cfg.approx_params()
    if cfg.ffn == "moe":
        d, f = cfg.d_model, cfg.d_ff
        routed_all = cfg.n_experts * 3 * d * f
        routed_active = cfg.top_k * 3 * d * f
        per_layer_delta = routed_all - routed_active
        n_moe_layers = cfg.n_layers - cfg.first_dense_layers
        n_params = n_params - n_moe_layers * per_layer_delta
    tokens = shape.global_batch * shape.seq_len
    if shape.kind == "train":
        return 6.0 * n_params * tokens
    if shape.kind == "prefill":
        return 2.0 * n_params * tokens
    return 2.0 * n_params * shape.global_batch  # decode: one token per seq
