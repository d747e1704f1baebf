"""Pallas TPU mega-kernel: one fused DGSEM Navier-Stokes RHS evaluation.

The periodic HIT RHS is ~a dozen separate XLA ops (primitive decode, BR1
gradient, eddy viscosity, three flux/divergence passes, forcing) with the
full nodal state written to and re-read from HBM between stages — each
intermediate is mesh-sized, so an RK5 substep moves ~30 state-sized buffers
through HBM per RHS call.  This kernel computes the whole evaluation —
DG derivative -> viscous/convective flux -> Smagorinsky eddy viscosity ->
divergence + forcing — in a single launch with every intermediate resident
in VMEM: per grid step it reads one block of (u, cs_nodes) and writes one
block of rhs.

Layout: the kernel works on the planar layout of kernels/ref.py — C planes
of (P, L) = (n^3 node rows, batch x K^3 element lanes) — so both minor dims
of every block are tile-dense (the natural (..., n, n, n, 5) layout ends in
(6, 5) at 24 DOF, which pads ~40x to the (8, 128) tile).
`fused_navier_stokes_rhs_planar` is the kernel on planar operands: the
solver's RK loop (`cfd/solver.advance_rl_interval`) converts its state to
the planar layout once per RL interval (`to_planar_batch`), steps it there
and converts back once (`from_planar_batch`).  `fused_navier_stokes_rhs`
is the natural-layout wrapper for single calls: it converts into and out
of the planar layout around each call.  A grid step holds
`block_e` WHOLE meshes, because the RHS is not element-local: the surface
exchange couples neighbour elements (periodic) and the Lundgren forcing
needs whole-box quadrature means — both stay in-kernel when the mesh is
resident.  `block_e` is rounded up so a block spans a multiple of 128 lanes
(2 meshes of 4^3 elements), or covers the whole batch when it is smaller.

The kernel body calls `ref.navier_stokes_rhs_planar` on its block — kernel
and oracle share one op order by construction, which is what the
`kernel_parity` gate (tests/test_kernel_parity.py) pins.  Internal math is
float32 regardless of I/O dtype; bf16 in/out serves the mixed-precision
rollout (HITConfig.precision = "bf16").

The wall-modelled channel (cfd/channel.py) runs the same body in its wall
variant (`ref.WallParams`: y walls with the modelled wall flux, the
in-kernel Reichardt inversion, constant C_s and pressure-gradient forcing)
on a Kx x Ky x Kz mesh with per-direction jacobians, as a Pallas call of
its own, `fused_wall_rhs` (`fused_wall_rhs_planar`), so a device trace
tells it from the periodic `fused_ns_rhs`.  Its second operand is the
(1, L) row of per-lane wall-stress scales, not a C_s plane.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref
from .policy import resolve_interpret, scoped_vmem_limit


def _kernel(u_ref, cs_ref, coef_ref, rmask_ref, lmask_ref, emask_ref, wq_ref,
            rhs_ref, *, n, k, **kw):
    consts = ref.PlanarConsts(coef=coef_ref, rmask=rmask_ref,
                              lmask=lmask_ref, emask=emask_ref, wq=wq_ref)
    planes = ref.navier_stokes_rhs_planar(u_ref, cs_ref, consts, n=n, k=k,
                                          roll=ref.kernel_roll, **kw)
    for c, plane in enumerate(planes):
        rhs_ref[c] = plane.astype(rhs_ref.dtype)


def _wall_kernel(u_ref, scale_ref, coef_ref, rmask_ref, lmask_ref,
                 emask_ref, wq_ref, gather_ref, scatter_ref, rhs_ref, *, n,
                 k, **kw):
    consts = ref.PlanarConsts(coef=coef_ref, rmask=rmask_ref,
                              lmask=lmask_ref, emask=emask_ref, wq=wq_ref)
    wall_consts = ref.WallConsts(gather=gather_ref, scatter=scatter_ref)
    planes = ref.navier_stokes_rhs_planar(
        u_ref, scale_ref, consts, n=n, k=k, wall_consts=wall_consts,
        roll=ref.kernel_roll, **kw)
    for c, plane in enumerate(planes):
        rhs_ref[c] = plane.astype(rhs_ref.dtype)


def envs_per_block(b: int, k, block_e: int) -> int:
    """Meshes per grid step: `block_e` rounded up to a 128-lane multiple,
    or the whole batch when it fits one such block.  k: K (a K^3 mesh) or
    (Kx, Ky, Kz)."""
    aligned = 128 // math.gcd(128, math.prod(ref.mesh3(k)))
    if b <= aligned:
        return b
    return aligned * max(1, -(-block_e // aligned))


def vmem_limit_bytes(n: int, lanes: int, wall: bool = False
                     ) -> int | None:
    """Scoped VMEM this block needs, or None if the default suffices.

    Counted in (P, lanes) f32 planes padded to the (8, 128) tile: the
    double-buffered u, rhs and cs blocks (22), the resident (P, 1)
    constant columns (3 (2n-1) + 7, each padded to 128 lanes) and 160
    planes of intermediates.  Mosaic's own count for the body at 128
    lanes is ~110 intermediate planes at n = 6 and ~145 at n = 8 (its
    out-of-VMEM reports for 24- and 32-DOF blocks on v5e).  The wall
    variant adds its 2n gather masks and 8 planes for the wall stress.
    """
    p = -(-n**3 // 8) * 8
    plane = p * max(128, -(-lanes // 128) * 128) * 4
    column = p * 128 * 4
    planes, columns = (8, 2 * n) if wall else (0, 0)
    return scoped_vmem_limit((22 + 160 + planes) * plane
                             + (3 * (2 * n - 1) + 7 + columns) * column)


@jax.named_scope("rhs.layout")   # repro.obs: its ops' scope
def to_planar_batch(u: jax.Array, cs_nodes: jax.Array,
                    block_e: int = 1) -> tuple[jax.Array, jax.Array, int]:
    """Natural-layout operands -> planar operands of the fused RHS.

    u: (..., K, K, K, n, n, n, 5); cs_nodes shaped like u[..., 0].  Returns
    (u_pl (5, n^3, L), cs_pl (n^3, L), meshes per grid step), the batch
    padded to a whole number of grid steps (`envs_per_block`) with copies
    of the first mesh: every padded lane is a valid flow state, so no
    inf/nan can come out of the discarded lanes.
    """
    mesh = u.shape[-7:]
    ub = u.reshape((-1,) + mesh)
    csb = cs_nodes.reshape((-1,) + mesh[:-1] + (1,))
    block_e = envs_per_block(ub.shape[0], mesh[0], block_e)
    pad = (-ub.shape[0]) % block_e
    if pad:
        ub = jnp.concatenate(
            [ub, jnp.broadcast_to(ub[:1], (pad,) + mesh)], axis=0)
        csb = jnp.concatenate(
            [csb, jnp.broadcast_to(csb[:1], (pad,) + csb.shape[1:])], axis=0)
    return ref.to_planar(ub), ref.to_planar(csb)[0], block_e


@jax.named_scope("rhs.layout")   # repro.obs: its ops' scope
def from_planar_batch(x: jax.Array, shape: tuple[int, ...]) -> jax.Array:
    """Inverse of `to_planar_batch`: (C, n^3, L) planes -> the natural
    `shape`, the padded meshes dropped."""
    b = math.prod(shape[:-7])
    return ref.from_planar(x, shape[-7:])[:b].reshape(shape)


@jax.named_scope("rhs.layout")   # repro.obs: its ops' scope
def to_planar_wall_batch(u: jax.Array, scale_elem: jax.Array,
                         block_e: int = 1
                         ) -> tuple[jax.Array, jax.Array, int]:
    """Natural-layout operands -> planar operands of the wall RHS.

    u: (..., Kx, Ky, Kz, n, n, n, 5); scale_elem: the per-element
    wall-stress scale (..., Kx, Ky, Kz), read on the wall elements only.
    Returns (u_pl (5, n^3, L), scale_pl (1, L), meshes per grid step),
    the batch padded as `to_planar_batch` pads it.
    """
    mesh = u.shape[-7:]
    ub = u.reshape((-1,) + mesh)
    sb = scale_elem.reshape((-1,) + mesh[:3])
    block_e = envs_per_block(ub.shape[0], mesh[:3], block_e)
    pad = (-ub.shape[0]) % block_e
    if pad:
        ub = jnp.concatenate(
            [ub, jnp.broadcast_to(ub[:1], (pad,) + mesh)], axis=0)
        sb = jnp.concatenate(
            [sb, jnp.broadcast_to(sb[:1], (pad,) + sb.shape[1:])], axis=0)
    return ref.to_planar(ub), sb.reshape(1, -1), block_e


@functools.partial(jax.jit, static_argnames=(
    "k", "block_e", "inv_w_end", "jac", "delta", "mu", "prandtl",
    "prandtl_turb", "wall", "interpret"))
def fused_wall_rhs_planar(
    u_pl: jax.Array,
    scale_pl: jax.Array,
    d_matrix: jax.Array,
    w: jax.Array,
    *,
    k: tuple[int, int, int],
    block_e: int,
    inv_w_end: tuple[float, float],
    jac: tuple[float, float, float],
    delta: float,
    mu: float,
    prandtl: float,
    prandtl_turb: float,
    wall: ref.WallParams,
    interpret: bool | None = None,
) -> jax.Array:
    """The wall-modelled channel's fused RHS on planar operands
    (`to_planar_wall_batch`'s layout): the Pallas call `fused_wall_rhs`.

    u_pl: (5, n^3, L) with L a multiple of the block's block_e Kx Ky Kz
    lanes; scale_pl: (1, L) per-lane wall-stress scales; k (Kx, Ky, Kz)
    with Ky >= 2; jac per direction; `wall` the static wall scalars.
    Returns the (5, n^3, L) RHS in u_pl's dtype.
    """
    n = d_matrix.shape[0]
    if k[1] < 2:
        raise ValueError(f"the wall variant needs Ky >= 2 elements, got {k}")
    p, lanes = n**3, block_e * math.prod(k)
    if u_pl.shape != (5, p, u_pl.shape[-1]) or u_pl.shape[-1] % lanes:
        raise ValueError(f"u_pl {u_pl.shape} is not (5, {p}, L) with L a "
                         f"multiple of {lanes} lanes")
    with jax.named_scope("rhs.layout"):
        consts = ref.planar_consts(d_matrix, w, n, k, block_e)
        wall_consts = ref.wall_consts(n)
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_wall_kernel, n=n, k=k, inv_w_end=inv_w_end,
                          jac=jac, delta=delta, mu=mu, prandtl=prandtl,
                          prandtl_turb=prandtl_turb, wall=wall),
        grid=(u_pl.shape[-1] // lanes,),
        in_specs=[
            pl.BlockSpec((5, p, lanes), lambda i: (0, 0, i)),
            pl.BlockSpec((1, lanes), lambda i: (0, i)),
            whole, whole, whole, whole, whole, whole, whole,
        ],
        out_specs=pl.BlockSpec((5, p, lanes), lambda i: (0, 0, i)),
        out_shape=jax.ShapeDtypeStruct(u_pl.shape, u_pl.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=vmem_limit_bytes(n, lanes, wall=True)),
        interpret=resolve_interpret(interpret),
        name="fused_wall_rhs",
    )(u_pl, scale_pl, *consts, *wall_consts)


@functools.partial(jax.jit, static_argnames=(
    "k", "block_e", "inv_w_end", "jac", "delta", "mu", "prandtl",
    "prandtl_turb", "forcing_a0", "k_tke", "interpret"))
def fused_navier_stokes_rhs_planar(
    u_pl: jax.Array,
    cs_pl: jax.Array,
    d_matrix: jax.Array,
    w: jax.Array,
    *,
    k: int,
    block_e: int,
    inv_w_end: tuple[float, float],
    jac: float,
    delta: float,
    mu: float,
    prandtl: float,
    prandtl_turb: float,
    forcing_a0: float,
    k_tke: float,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused RHS on planar operands (`to_planar_batch`'s layout).

    u_pl: (5, n^3, L) with L a multiple of the block's block_e K^3 lanes;
    cs_pl: (n^3, L); d_matrix (n, n); w (n,) GLL weights; scalars as in
    the oracle.  Returns the (5, n^3, L) RHS in u_pl's dtype.
    """
    n = d_matrix.shape[0]
    p, lanes = n**3, block_e * k**3
    if u_pl.shape != (5, p, u_pl.shape[-1]) or u_pl.shape[-1] % lanes:
        raise ValueError(f"u_pl {u_pl.shape} is not (5, {p}, L) with L a "
                         f"multiple of {lanes} lanes")
    with jax.named_scope("rhs.layout"):
        consts = ref.planar_consts(d_matrix, w, n, k, block_e)
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_kernel, n=n, k=k, inv_w_end=inv_w_end, jac=jac,
                          delta=delta, mu=mu, prandtl=prandtl,
                          prandtl_turb=prandtl_turb, forcing_a0=forcing_a0,
                          k_tke=k_tke),
        grid=(u_pl.shape[-1] // lanes,),
        in_specs=[
            pl.BlockSpec((5, p, lanes), lambda i: (0, 0, i)),
            pl.BlockSpec((p, lanes), lambda i: (0, i)),
            whole, whole, whole, whole, whole,
        ],
        out_specs=pl.BlockSpec((5, p, lanes), lambda i: (0, 0, i)),
        out_shape=jax.ShapeDtypeStruct(u_pl.shape, u_pl.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=vmem_limit_bytes(n, lanes)),
        interpret=resolve_interpret(interpret),
        name="fused_ns_rhs",
    )(u_pl, cs_pl, *consts)


@functools.partial(jax.jit, static_argnames=(
    "inv_w_end", "jac", "delta", "mu", "prandtl", "prandtl_turb",
    "forcing_a0", "k_tke", "block_e", "interpret"))
def fused_navier_stokes_rhs(
    u: jax.Array,
    cs_nodes: jax.Array,
    d_matrix: jax.Array,
    w: jax.Array,
    *,
    inv_w_end: tuple[float, float],
    jac: float,
    delta: float,
    mu: float,
    prandtl: float,
    prandtl_turb: float,
    forcing_a0: float,
    k_tke: float,
    block_e: int = 1,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused RHS for an arbitrary batch of HIT meshes.

    u: (..., K, K, K, n, n, n, 5); cs_nodes shaped like u[..., 0];
    d_matrix (n, n); w (n,) GLL weights; scalars as in the oracle.  Returns
    the RHS with u's shape and dtype.  Matches ref.navier_stokes_rhs_fused.
    Everything but the kernel is layout work, under the named scope
    `rhs.layout` (repro.obs), so the device trace tells it from the kernel.
    """
    u_pl, cs_pl, block_e = to_planar_batch(u, cs_nodes, block_e)
    out = fused_navier_stokes_rhs_planar(
        u_pl, cs_pl, d_matrix, w, k=u.shape[-7], block_e=block_e,
        inv_w_end=inv_w_end, jac=jac, delta=delta, mu=mu, prandtl=prandtl,
        prandtl_turb=prandtl_turb, forcing_a0=forcing_a0, k_tke=k_tke,
        interpret=interpret)
    return from_planar_batch(out, u.shape)
