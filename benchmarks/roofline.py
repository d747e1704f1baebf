"""Roofline table: reads the dry-run artifacts (launch/dryrun.py) and prints
the per-(arch x shape) compute/memory/collective terms — the §Roofline
source of EXPERIMENTS.md.  Run the dry-run first:

    python -m repro.launch.dryrun --all --mesh both
"""
from __future__ import annotations

import glob
import json
import os

from . import common

DRYRUN_DIR = os.path.join(common.ARTIFACTS, "dryrun")


def load(mesh: str = "single", tag: str = "") -> list[dict]:
    from repro.configs.shapes import SHAPES
    rows = []
    for path in sorted(glob.glob(os.path.join(DRYRUN_DIR, f"{mesh}_*.json"))):
        base = os.path.basename(path)
        untagged = any(base.endswith(f"_{s}.json") for s in SHAPES)
        if tag and not base.endswith(f"_{tag}.json"):
            continue
        if not tag and not untagged:
            continue
        with open(path) as f:
            rows.append(json.load(f))
    return rows


def print_table(mesh: str = "single", tag: str = "") -> list[dict]:
    rows = load(mesh, tag)
    common.row("# roofline", "arch", "shape", "status", "bound",
               "compute_s", "memory_s", "memory_raw_s", "collective_s",
               "roofline_frac", "useful_flop_ratio")
    for r in rows:
        if r["status"] != "ok":
            common.row("roofline", r["arch"], r["shape"], r["status"],
                       r.get("reason", r.get("error", ""))[:60], "", "", "",
                       "", "", "")
            continue
        t = r["roofline"]
        ratio = r.get("useful_flop_ratio")
        common.row("roofline", r["arch"], r["shape"], "ok", t["bound"],
                   f"{t['compute_s']:.4f}", f"{t['memory_s']:.4f}",
                   f"{t.get('memory_raw_s', t['memory_s']):.4f}",
                   f"{t['collective_s']:.4f}",
                   f"{t['roofline_fraction']:.3f}",
                   f"{ratio:.2f}" if ratio else "")
    return rows


def rhs_kernel_entry(quick: bool = True) -> dict:
    """Arithmetic-intensity entry for the fused DGSEM-RHS mega-kernel.

    Compiles the pure-jnp reference RHS and reads XLA's own cost analysis
    (flops, bytes accessed), then
    contrasts the unfused arithmetic intensity with the fused ideal — the
    mega-kernel touches HBM only for the state in, cs field in and RHS out
    (every intermediate lives in VMEM), so its AI is flops over that
    minimal traffic.  Writes roofline_rhs.json.
    """
    import jax
    import jax.numpy as jnp

    from repro.cfd import initial, solver
    from repro.cfd.solver import HITConfig

    cases = [("hit_reduced", HITConfig(n_poly=3, n_elem=2,
                                       use_kernels=False))]
    if not quick:
        cases.append(("hit_24dof", HITConfig(n_poly=5, n_elem=4,
                                             use_kernels=False)))
    common.row("# roofline_rhs", "case", "flops", "bytes_unfused",
               "bytes_fused_ideal", "ai_unfused", "ai_fused")
    entries = []
    for name, cfg in cases:
        ops_d = cfg.operators()
        u = initial.sample_initial_state(jax.random.PRNGKey(0), cfg)
        cs = jnp.full(u.shape[:-1], 0.17, u.dtype)
        compiled = jax.jit(
            lambda u, cs: solver.navier_stokes_rhs(u, cs, cfg, ops_d)
        ).lower(u, cs).compile()
        cost = compiled.cost_analysis()
        flops = float(cost.get("flops", 0.0))
        bytes_unfused = float(cost.get("bytes accessed", 0.0))
        # fused ideal: read state + cs, write rhs — intermediates in VMEM
        bytes_fused = float((2 * u.size + cs.size) * u.dtype.itemsize)
        entry = {
            "case": name,
            "flops": flops,
            "bytes_unfused": bytes_unfused,
            "bytes_fused_ideal": bytes_fused,
            "ai_unfused": flops / bytes_unfused if bytes_unfused else None,
            "ai_fused": flops / bytes_fused if bytes_fused else None,
        }
        entries.append(entry)
        common.row("roofline_rhs", name, f"{flops:.3e}",
                   f"{bytes_unfused:.3e}", f"{bytes_fused:.3e}",
                   f"{entry['ai_unfused']:.1f}" if entry["ai_unfused"]
                   else "", f"{entry['ai_fused']:.1f}"
                   if entry["ai_fused"] else "")
    common.save_json("roofline_rhs.json", {"entries": entries})
    return {"n_rhs_entries": len(entries)}


def run(quick: bool = True) -> dict:
    out = rhs_kernel_entry(quick=quick)
    if not os.path.isdir(DRYRUN_DIR) or not os.listdir(DRYRUN_DIR):
        print("no dry-run artifacts found; run "
              "`python -m repro.launch.dryrun --all --mesh both` first")
        return out
    rows = print_table("single")
    ok = [r for r in rows if r["status"] == "ok"]
    if ok:
        worst = min(ok, key=lambda r: r["roofline"]["roofline_fraction"])
        coll = max(ok, key=lambda r: r["roofline"]["collective_s"])
        common.row("# hillclimb-candidates",
                   f"worst_fraction={worst['arch']}/{worst['shape']}",
                   f"most_collective={coll['arch']}/{coll['shape']}")
    return {**out, "n_cells": len(rows)}


if __name__ == "__main__":
    run()
