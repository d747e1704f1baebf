"""Algorithmic operation and byte counts of the wall-modelled channel RHS.

Written from the mathematics and the shapes of a call, as bench/counts/
hit.py is, so a faster implementation of the same work reads the same
count.  A floating-point operation is one add, multiply, divide, square
root, abs, max, exp or log1p.

Per node the channel does the HIT RHS's work (`hit.rhs_flops_per_node`)
with two changes: the constant pressure-gradient forcing (rho f_x, its
work rho f_x u_x, two adds: 4) replaces Lundgren's (29); and along y each
column of n^2 face points has Ky - 1 interior faces, not Ky, so one LLF
and one viscous face flux per column fall away (37 + 8).  Per wall-face
column (2 Kx Kz n^2 per mesh, both walls; see `wall_column_flops`):
  y-quadrature mean of rho, rho u_x, rho u_z   6n
  matching-point velocity u_x, u_z              2
  |u_par|                                       5
  laminar initial guess sqrt(nu u / y_m + eps)  4
  per Reichardt round (x wm_iters)              20:
      y+ = y_m u_tau / nu                       2
      log1p(kappa y+) / kappa                   3
      7.8 (1 - e^(-y+/11) - (y+/11) e^(-y+/3))  9
      sum, max                                  2
      sqrt(u_tau u / u+ + eps)                  4
  tau = a rho u_tau^2                           3
  stress components tau u_x / |u|, tau u_z / |u|  4
The wall flux itself (pressure and stress in place of the face fluxes) is
a selection and costs nothing.
Minimal HBM bytes of one call: u read and the RHS written (5 channels
each) at the call's item size, and one wall-stress scale per element.
"""
from __future__ import annotations

from bench.counts import hit

LUNDGREN_FLOPS = 29
PRESSURE_GRADIENT_FLOPS = 4
FACE_FLUX_FLOPS = 37 + 8          # LLF and viscous flux at one face point
REICHARDT_ROUND_FLOPS = 20


def wall_column_flops(n: int, wm_iters: int) -> float:
    return 6.0 * n + 2 + 5 + 4 + REICHARDT_ROUND_FLOPS * wm_iters + 3 + 4


def rhs_call(n: int, n_elem: tuple[int, int, int], wm_iters: int,
             batch: int, itemsize: int = 4) -> tuple[float, float]:
    """(flops, minimal HBM bytes) of one wall RHS evaluation of `batch`
    meshes of Kx x Ky x Kz elements with n^3 nodes each."""
    kx, ky, kz = n_elem
    cells = batch * kx * ky * kz
    nodes = cells * n**3
    per_node = (hit.rhs_flops_per_node(n) - LUNDGREN_FLOPS
                + PRESSURE_GRADIENT_FLOPS)
    y_columns = batch * kx * kz * n * n
    flops = (per_node * nodes - FACE_FLUX_FLOPS * y_columns
             + 2 * y_columns * wall_column_flops(n, wm_iters))
    return float(flops), float(nodes * 10 * itemsize + cells * itemsize)


def rhs_call_from_state(shape: tuple[int, ...], wm_iters: int,
                        itemsize: int = 4) -> tuple[float, float]:
    """The same, read off a state of shape (..., Kx, Ky, Kz, n, n, n, 5)."""
    batch = 1
    for s in shape[:-7]:
        batch *= s
    return rhs_call(shape[-4], tuple(shape[-7:-4]), wm_iters, batch,
                    itemsize)
