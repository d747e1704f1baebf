"""Layer-1 kernel auditor: static checks over every Pallas kernel.

Each registered kernel is traced (never executed) at a small but
structurally representative shape, and its `pallas_call` equations are
inspected:

KERN001  the kernel closes over an array constant.  Pallas lowers closure
         constants by materializing them per launch; on TPU this either
         fails outright or silently stages the array through HBM on every
         grid step.  The fix is always the same: pass the array as a real
         input with its own BlockSpec (PR 6's `d_matrix` lesson).
KERN002  a block shape that does not divide its (padded) array dim — the
         callers' `(-n) % block` padding contract was broken, so the last
         grid step reads/writes a partial block.
KERN003  estimated VMEM working set above the kernel's scoped-VMEM
         limit (its `vmem_limit_bytes`, else the TPU default).  Each block
         is padded to the (8, 128) vreg tile (16 / 32 sublanes for 2- / 1-
         byte dtypes) before it is counted, and pipelined blocks count
         twice (double buffering); whole-array VMEM operands count once.
         An estimate, not a compiler bound: intermediates are not counted,
         and tests/test_tpu_compile.py compiles the real kernels for v5e.

The registry below pins every kernel entry point in `src/repro/kernels/`;
`tests/test_analysis.py` red-teams each rule with a deliberately bad
kernel.
"""
from __future__ import annotations

from typing import Callable

import math

import jax
from jax.extend import core as jcore

from .report import Finding, Report

# Default scoped-VMEM limit of a TPU v5e kernel, less headroom for compiler
# scratch; a kernel that raises `vmem_limit_bytes` is held to that instead.
VMEM_BUDGET_MB = 12.0
_LANES = 128


def _block_dims(block_shape) -> tuple[int, ...]:
    """Block dims as ints: `Squeezed` dims count 1, `Blocked` their size."""
    return tuple(d if isinstance(d, int)
                 else getattr(d, "block_size", None) or 1
                 for d in block_shape)


def padded_block_bytes(blk: tuple[int, ...], itemsize: int) -> int:
    """Bytes one VMEM buffer of this block occupies once its two minor dims
    are padded to the vreg tile (sublanes x 128 lanes; 8 sublanes for
    4-byte dtypes, 16 for 2-byte, 32 for 1-byte).  A rank-1 block is laid
    out as whole vregs."""
    sub = 8 * max(1, 4 // itemsize)
    if len(blk) == 1:
        return -(-blk[0] // (sub * _LANES)) * sub * _LANES * itemsize
    rows = -(-blk[-2] // sub) * sub
    cols = -(-blk[-1] // _LANES) * _LANES
    return math.prod(blk[:-2]) * rows * cols * itemsize


def _vmem_limit_mb(eqn) -> float | None:
    params = eqn.params.get("compiler_params") or {}
    limit = getattr(params.get("mosaic_tpu"), "vmem_limit_bytes", None)
    return None if limit is None else limit / 2**20


def _kernel_cases() -> dict[str, Callable[[], tuple]]:
    """name -> builder returning (fn, args, kwargs); traced, not run."""
    import jax.numpy as jnp

    def dg_derivative3():
        from ..kernels.dg_derivative import dg_derivative3 as fn
        u = jnp.zeros((4, 4, 4, 4, 5), jnp.float32)
        d = jnp.zeros((4, 4), jnp.float32)
        return fn, (u, d), dict(block_b=2, interpret=True)

    def smagorinsky_nut():
        from ..kernels.smagorinsky import smagorinsky_nut as fn
        g = jnp.zeros((96, 3, 3), jnp.float32)
        cs = jnp.zeros((96,), jnp.float32)
        return fn, (g, cs), dict(delta=0.1, block_p=32, interpret=True)

    def wall_model_tau():
        from ..kernels.wall_model import wall_model_tau as fn
        up = jnp.ones((64,), jnp.float32)
        rw = jnp.ones((64,), jnp.float32)
        return fn, (up, rw), dict(y_m=0.1, nu=1e-3, block_p=32,
                                  interpret=True)

    def fused_rhs():
        from ..cfd.solver import HITConfig
        from ..kernels.rhs import fused_navier_stokes_rhs as fn
        cfg = HITConfig(n_poly=3, n_elem=2, use_kernels=False)
        ops = cfg.operators()
        u = jnp.zeros((2, 2, 2, 4, 4, 4, 5), jnp.float32)
        cs = jnp.zeros((2, 2, 2, 4, 4, 4), jnp.float32)
        return fn, (u, cs, ops["D"], ops["w"]), dict(
            inv_w_end=ops["inv_w_end"], jac=cfg.dg.jac,
            delta=cfg.delta_filter, mu=cfg.gas.mu, prandtl=cfg.prandtl,
            prandtl_turb=cfg.prandtl_turb, forcing_a0=cfg.forcing_a0,
            k_tke=cfg.k_tke, interpret=True)

    def fused_rhs_planar():
        from ..cfd.solver import HITConfig
        from ..kernels.rhs import fused_navier_stokes_rhs_planar as fn
        cfg = HITConfig(n_poly=3, n_elem=2, use_kernels=False)
        ops = cfg.operators()
        # 32 meshes of 2^3 elements: two 128-lane blocks of 16 meshes
        u = jnp.zeros((5, 64, 256), jnp.float32)
        cs = jnp.zeros((64, 256), jnp.float32)
        return fn, (u, cs, ops["D"], ops["w"]), dict(
            k=2, block_e=16, inv_w_end=ops["inv_w_end"], jac=cfg.dg.jac,
            delta=cfg.delta_filter, mu=cfg.gas.mu, prandtl=cfg.prandtl,
            prandtl_turb=cfg.prandtl_turb, forcing_a0=cfg.forcing_a0,
            k_tke=cfg.k_tke, interpret=True)

    def wall_rhs_planar():
        from ..cfd import channel
        from ..cfd.channel import ChannelConfig
        from ..kernels.rhs import fused_wall_rhs_planar as fn
        cfg = ChannelConfig(n_elem=(2, 4, 2), use_kernels=False)
        ops = cfg.operators()
        # 16 meshes of 2 x 4 x 2 elements: two 128-lane blocks of 8 meshes
        u = jnp.zeros((5, 64, 256), jnp.float32)
        scale = jnp.ones((1, 256), jnp.float32)
        return fn, (u, scale, ops["D"], ops["w"]), dict(
            k=cfg.n_elem, block_e=8, interpret=True,
            **channel.wall_rhs_scalars(cfg, ops))

    def flash_attention():
        from ..kernels.flash_attention import flash_attention as fn
        q = jnp.zeros((1, 2, 64, 16), jnp.float32)
        kv = jnp.zeros((1, 2, 64, 16), jnp.float32)
        return fn, (q, kv, kv), dict(block_q=32, block_k=32,
                                     interpret=True)

    def linear_scan():
        from ..kernels.linear_scan import linear_scan as fn
        x = jnp.zeros((2, 32, 8), jnp.float32)
        v = jnp.zeros((2, 32, 4), jnp.float32)
        return fn, (x, x, v, x), dict(chunk=16, interpret=True)

    return {
        "dg_derivative3": dg_derivative3,
        "smagorinsky_nut": smagorinsky_nut,
        "wall_model_tau": wall_model_tau,
        "fused_rhs": fused_rhs,
        "fused_rhs_planar": fused_rhs_planar,
        "wall_rhs_planar": wall_rhs_planar,
        "flash_attention": flash_attention,
        "linear_scan": linear_scan,
    }


def _walk_pallas_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for val in eqn.params.values():
            for item in (val if isinstance(val, (list, tuple)) else (val,)):
                if isinstance(item, jcore.ClosedJaxpr):
                    yield from _walk_pallas_eqns(item.jaxpr)
                elif isinstance(item, jcore.Jaxpr):
                    yield from _walk_pallas_eqns(item)


def audit_kernel(name: str, fn, args: tuple, kwargs: dict,
                 vmem_budget_mb: float = VMEM_BUDGET_MB
                 ) -> tuple[list[Finding], dict]:
    """Findings + {'vmem_mb': estimate} for one traced kernel call."""
    findings: list[Finding] = []
    try:
        closed = jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
    except ValueError as e:
        # jax raises eagerly at trace time for closure-captured arrays
        # ("Pallas kernel captures constants ... pass them as inputs")
        if "constant" in str(e).lower():
            return [Finding(rule="KERN001", entrypoint=name,
                            message=f"kernel captures array constants "
                                    f"({str(e).splitlines()[0][:140]})")], {}
        raise

    vmem_bytes = 0
    budget_mb = vmem_budget_mb
    for eqn in _walk_pallas_eqns(closed.jaxpr):
        inner = eqn.params.get("jaxpr")
        const_avals = [v.aval for v in getattr(inner, "constvars", ())]
        big = [a for a in const_avals if getattr(a, "size", 0) > 1]
        if big:
            findings.append(Finding(
                rule="KERN001", entrypoint=name,
                message=f"kernel closes over {len(big)} array constant(s) "
                        f"{[tuple(a.shape) for a in big]} — pass them as "
                        "inputs with BlockSpecs"))
        gm = eqn.params.get("grid_mapping")
        if gm is None:
            continue
        limit_mb = _vmem_limit_mb(eqn)
        if limit_mb is not None:
            budget_mb = limit_mb
        for bm in gm.block_mappings:
            arr = bm.array_aval
            blk = _block_dims(bm.block_shape)
            whole = getattr(bm.transformed_block_aval, "memory_space",
                            None) is not None
            vmem_bytes += (padded_block_bytes(blk, arr.dtype.itemsize)
                           * (1 if whole else 2))
            for b, n in zip(blk, arr.shape):
                if b and n % b != 0:
                    findings.append(Finding(
                        rule="KERN002", entrypoint=name,
                        message=f"block dim {b} does not divide padded "
                                f"array dim {n} (block {blk} vs array "
                                f"{tuple(arr.shape)})"))
    mb = vmem_bytes / 2**20
    if mb > budget_mb:
        findings.append(Finding(
            rule="KERN003", entrypoint=name,
            message=f"estimated VMEM working set {mb:.2f} MiB exceeds the "
                    f"{budget_mb} MiB budget"))
    return findings, {"vmem_mb": round(mb, 4)}


def run(report: Report | None = None) -> Report:
    report = report or Report()
    stats = {}
    for name, build in _kernel_cases().items():
        fn, args, kwargs = build()
        findings, meta = audit_kernel(name, fn, args, kwargs)
        report.extend(findings)
        stats[name] = meta
    report.meta.setdefault("kernel_audit", {})["kernels"] = stats
    return report
