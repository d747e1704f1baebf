"""Pure-jnp oracles for every Pallas kernel in this package.

These are the *semantic contracts*: tests sweep shapes/dtypes and assert
allclose(kernel(interpret=True), ref).  They are also the implementations
used on non-TPU backends and inside the multi-pod dry-run (Pallas lowers for
TPU; the CPU dry-run must still produce a compilable, cost-analyzable HLO,
and the chunked/flash reference forms below have the same asymptotic
FLOP/byte behavior as the kernels).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas import tpu as pltpu


# --- dg_derivative -----------------------------------------------------------
def dg_derivative3(u: jax.Array, d_matrix: jax.Array) -> tuple[jax.Array, ...]:
    """Fused 3-direction DGSEM derivative.

    u: (B, n, n, n, C) element batch; d_matrix: (n, n).
    Returns (du0, du1, du2) with du_d = derivative along intra-element axis d.
    """
    du0 = jnp.einsum("im,bmjkc->bijkc", d_matrix, u)
    du1 = jnp.einsum("jm,bimkc->bijkc", d_matrix, u)
    du2 = jnp.einsum("km,bijmc->bijkc", d_matrix, u)
    return du0, du1, du2


# --- smagorinsky -------------------------------------------------------------
def smagorinsky_nut(grad_v: jax.Array, cs: jax.Array, delta: float) -> jax.Array:
    """Fused strain-rate -> eddy-viscosity chain (paper Eq. 3).

    grad_v: (P, 3, 3) with grad_v[p, i, j] = d v_i / d x_j at point p.
    cs:     (P,) per-point Smagorinsky coefficient (element value broadcast).
    Returns nu_t: (P,) = (cs * delta)^2 * sqrt(2 S_ij S_ij).
    """
    s = 0.5 * (grad_v + jnp.swapaxes(grad_v, -1, -2))
    s_mag = jnp.sqrt(2.0 * jnp.sum(s * s, axis=(-1, -2)) + 1e-30)
    return (cs * delta) ** 2 * s_mag


# --- wall model --------------------------------------------------------------
def reichardt_uplus(y_plus, kappa: float = 0.41, xp=jnp):
    """Reichardt's composite law of the wall u+(y+): blends the viscous
    sublayer (u+ = y+), buffer layer and log law smoothly — valid at every
    y+, which is what lets one formula serve both the wall model and the
    reference profile at smoke-scale Reynolds numbers.  `xp` lets the same
    formula run under numpy for config-time reference profiles
    (cfd.channel re-exports this)."""
    return (xp.log1p(kappa * y_plus) / kappa
            + 7.8 * (1.0 - xp.exp(-y_plus / 11.0)
                     - (y_plus / 11.0) * xp.exp(-y_plus / 3.0)))


def wall_model_tau(u_par: jax.Array, rho_w: jax.Array, *, y_m: float,
                   nu: float, kappa: float = 0.41,
                   iters: int = 8) -> jax.Array:
    """tau_w = rho u_tau^2 by inverting u_par/u_tau = u+(y_m u_tau / nu).

    Geometrically-damped fixed point: in the viscous limit (u+ ~ y+) the
    damped map lands on the exact laminar stress mu u_par / y_m in one step,
    and in the log regime it contracts; `iters` iterations unroll into the
    jitted RHS.  Oracle for kernels/wall_model.py (identical op order).
    """
    f32 = jnp.float32
    tau = reichardt_stress(u_par.astype(f32), rho_w.astype(f32), y_m=y_m,
                           nu=nu, kappa=kappa, iters=iters)
    return tau.astype(u_par.dtype)


def reichardt_stress(up, rho_w, *, y_m: float, nu: float, kappa: float,
                     iters: int):
    """The fixed point of `wall_model_tau` on float32 operands: laminar
    initial guess, `iters` damped rounds, rho u_tau^2."""
    u_tau = jnp.sqrt(nu * up / y_m + 1e-12)  # laminar initial guess
    for _ in range(iters):
        y_plus = y_m * u_tau / nu
        u_plus = jnp.maximum(reichardt_uplus(y_plus, kappa), 1e-6)
        u_tau = jnp.sqrt(u_tau * up / u_plus + 1e-14)
    return rho_w * u_tau**2


# --- fused Navier-Stokes RHS -------------------------------------------------
# Self-contained single-pass DGSEM RHS — the oracle for kernels/rhs.py.  The
# kernel body calls `navier_stokes_rhs_planar` on its VMEM block and this
# module's `navier_stokes_rhs_fused` calls the same function on the whole
# batch, so kernel and oracle share one op order by construction.  The
# constants and formulas mirror cfd/equations + cfd/dgsem (+ cfd/channel for
# the wall variant); they are restated here because this module must stay a
# leaf (imports jax only — the kernels cannot cycle through the cfd package).
#
# Planar layout.  The state (..., Kx, Ky, Kz, n, n, n, C) is held as C
# planes of shape (P, L): P = n^3 node rows (r = i0 n^2 + i1 n + i2) by
# L = batch x Kx Ky Kz element lanes (l = b Kx Ky Kz + e0 Ky Kz + e1 Kz +
# e2).  Both minor dims are tile-dense for Mosaic (P is a multiple of 8 for
# even n, a grid block spans a multiple of 128 lanes), and every operation
# is elementwise, a static roll, a select or a reduction:
#   * node-axis derivatives (and the split-form two-point sums) are sums
#     over row offsets o of D[i, i+o] * roll(x, -o s_d) with s_d the row
#     stride of node axis d — the per-row coefficients come in as (P, 1)
#     columns (`planar_consts`), zero where i+o falls outside the element;
#   * the periodic neighbour exchange is a row roll by (n-1) s_d (lo face
#     <-> hi face of the same element) plus a lane roll by the element
#     stride t_d with a select at the periodic wrap;
#   * the surface lift is a select on the face rows, so interior rows keep
#     their volume term exactly (the + 0 of the cfd reference);
#   * the Lundgren forcing's whole-box means are a weighted row sum and a
#     per-environment masked lane sum.
# Face quantities are evaluated on every row and read only on the face
# rows, so no operation changes shape — except in the wall variant's
# matching-point inversion (`_wall_stress`), which runs on the n^2 wall-face
# columns gathered into the leading rows.
#
# Wall variant (`WallParams`, the wall-modelled channel of cfd/channel.py):
# y is walled, x and z periodic.  On the first / last element lanes along y
# the wrapped faces take the wall values: the BR1 gradient trace is the
# interior trace with v_y = 0, and the +y numerical flux is the
# no-penetration pressure flux minus the modelled stress tau_w = a rho
# u_tau^2 along the matching-point velocity (Reichardt's law inverted at the
# y-quadrature mean of the wall element's column).  C_s is a constant and
# the forcing is the constant pressure gradient f_x.

_GAMMA = 1.4
_R_GAS = 1.0
_CP = _GAMMA * _R_GAS / (_GAMMA - 1.0)


class PlanarConsts(NamedTuple):
    """Per-layout constants of the planar RHS (built by `planar_consts`).

    coef  (3, 2n-1, P, 1)  D[i_d, i_d + o] per node axis d and row offset
                           o = -(n-1)..n-1, zero outside the element
    rmask (6, P, 1)        1.0 on the lo / hi face rows of node axis d
                           (order lo0, hi0, lo1, hi1, lo2, hi2)
    lmask (6, 1, L)        1.0 on the first / last element lanes of
                           element axis d (order first0, last0, ...)
    emask (E, 1, L)        1.0 on the lanes of environment j
    wq    (P, 1)           quadrature weight of each node (unit mass)
    """

    coef: jax.Array
    rmask: jax.Array
    lmask: jax.Array
    emask: jax.Array
    wq: jax.Array


class WallConsts(NamedTuple):
    """Row masks of the wall variant's column gather (`wall_consts`).

    gather  (n, P, 1)  1.0 on the gathered rows i0 n .. i0 n + n-1 that
                       receive the y-lo face row (i0, 0, i2) of node0 i0
    scatter (n, P, 1)  1.0 on the y-lo face rows (i0, 0, i2) of node0 i0
    """

    gather: jax.Array
    scatter: jax.Array


class WallParams(NamedTuple):
    """Static scalars of the wall variant (all hashable, jit-static).

    y_m the matching height, nu the kinematic viscosity, kappa and iters
    the Reichardt inversion, f_x the pressure-gradient forcing, cs the
    interior Smagorinsky coefficient, w_half the GLL weights over 2 (the
    y-quadrature of the matching mean)."""

    y_m: float
    nu: float
    kappa: float
    iters: int
    f_x: float
    cs: float
    w_half: tuple[float, ...]


def mesh3(k) -> tuple[int, int, int]:
    """Element counts (Kx, Ky, Kz) of a cubic K or an anisotropic grid."""
    return (k, k, k) if isinstance(k, int) else tuple(k)


def to_planar(u: jax.Array) -> jax.Array:
    """(B, Kx, Ky, Kz, n, n, n, C) -> (C, n^3, B Kx Ky Kz)."""
    b, kx, ky, kz, n, _, _, c = u.shape
    return jnp.transpose(u, (7, 4, 5, 6, 0, 1, 2, 3)).reshape(
        c, n**3, b * kx * ky * kz)


def from_planar(x: jax.Array, mesh: tuple[int, ...]) -> jax.Array:
    """Inverse of `to_planar`; `mesh` is (Kx, Ky, Kz, n, n, n, C)."""
    kx, ky, kz, n, _, _, c = mesh
    b = x.shape[-1] // (kx * ky * kz)
    x = x.reshape(c, n, n, n, b, kx, ky, kz)
    return jnp.transpose(x, (4, 5, 6, 7, 1, 2, 3, 0))


def _node_index(n: int) -> list[np.ndarray]:
    """Node index along each node axis of every planar row (host table)."""
    rows = np.arange(n**3)
    return [rows // n ** (2 - d) % n for d in range(3)]


def _deriv_index(n: int) -> list[tuple[np.ndarray, ...]]:
    """Host tables of `deriv_coef`, per node axis: the row's node index and
    its partner's i_d + o (clipped), and whether the partner is inside the
    element."""
    offsets = np.arange(-(n - 1), n)[:, None]
    out = []
    for node in _node_index(n):
        m = node[None, :] + offsets
        out.append((np.broadcast_to(node, m.shape), np.clip(m, 0, n - 1),
                    (m >= 0) & (m < n)))
    return out


def deriv_coef(d_matrix: jax.Array, n: int) -> jax.Array:
    """(3, 2n-1, n^3, 1): D[i_d, i_d + o] per node axis d and row offset
    o = -(n-1)..n-1 of every planar row, zero where i_d + o is outside the
    element."""
    d32 = d_matrix.astype(jnp.float32)
    coef = [jnp.where(inside, d32[rows, cols], 0.0)
            for rows, cols, inside in _deriv_index(n)]
    return jnp.stack(coef)[..., None]


def planar_deriv(x, coef, n: int, d: int, roll=jnp.roll):
    """sum_m D[i_d, m] x[.., m, ..] along node axis d of a (P, L) plane;
    `coef` from `deriv_coef` (array or VMEM ref)."""
    s = n ** (2 - d)
    out = None
    for j, o in enumerate(range(-(n - 1), n)):
        shift = -o * s % x.shape[0]
        term = coef[d, j] * (roll(x, shift, 0) if shift else x)
        out = term if out is None else out + term
    return out


def _elem_strides(k) -> tuple[int, int, int]:
    """Lane stride of each element axis: (Ky Kz, Kz, 1)."""
    _, ky, kz = mesh3(k)
    return (ky * kz, kz, 1)


def _planar_masks(n: int, k, n_env: int) -> tuple[np.ndarray, ...]:
    """Host tables of `planar_consts`: the float32 rmask, lmask, emask."""
    node = _node_index(n)
    km, t = mesh3(k), _elem_strides(k)
    cells = km[0] * km[1] * km[2]
    rmask = np.stack([node[d] == e for d in range(3) for e in (0, n - 1)])
    lanes = np.arange(n_env * cells)
    elem = [lanes // t[d] % km[d] for d in range(3)]
    lmask = np.stack([elem[d] == e for d in range(3)
                      for e in (0, km[d] - 1)])
    emask = lanes[None, :] // cells == np.arange(n_env)[:, None]
    f32 = np.float32
    return (rmask.astype(f32)[..., None], lmask.astype(f32)[:, None, :],
            emask.astype(f32)[:, None, :])


def planar_consts(d_matrix: jax.Array, w: jax.Array, n: int, k,
                  n_env: int) -> PlanarConsts:
    """Constants for `n_env` meshes of n^3-node elements, K^3 (an int k)
    or Kx x Ky x Kz (a 3-tuple).  The masks and index tables are built on
    the host, so a compiled program holds them as literals and computes
    nothing for them per call."""
    node = _node_index(n)
    rmask, lmask, emask = _planar_masks(n, k, n_env)
    w2 = w.astype(jnp.float32) * 0.5  # reference [-1,1] -> unit mass
    wq = w2[node[0]] * w2[node[1]] * w2[node[2]]
    return PlanarConsts(
        coef=deriv_coef(d_matrix, n),
        rmask=jnp.asarray(rmask),
        lmask=jnp.asarray(lmask),
        emask=jnp.asarray(emask),
        wq=wq[:, None])


def wall_consts(n: int) -> WallConsts:
    """Host-built row masks of the wall variant's column gather."""
    rows = np.arange(n**3)
    node = _node_index(n)
    gather = np.stack([(rows // n == i0) & (rows < n * n)
                       for i0 in range(n)])
    scatter = np.stack([(node[0] == i0) & (node[1] == 0)
                        for i0 in range(n)])
    f32 = np.float32
    return WallConsts(gather=jnp.asarray(gather.astype(f32)[..., None]),
                      scatter=jnp.asarray(scatter.astype(f32)[..., None]))


def kernel_roll(x, shift: int, axis: int):
    """`jnp.roll` by a static shift as Mosaic's rotate (kernel bodies);
    the same values as `jnp.roll`, which the XLA oracle uses."""
    return pltpu.roll(x, shift, axis)


class _Planar:
    """Stencil operators of one planar block (n^3 node rows, Kx Ky Kz
    element lanes per environment)."""

    def __init__(self, consts, n: int, k, roll):
        self.c, self.n, self._roll_fn = consts, n, roll
        self.k, self.t = mesh3(k), _elem_strides(k)

    def roll(self, x, shift: int, axis: int):
        shift %= x.shape[axis]
        return x if shift == 0 else self._roll_fn(x, shift, axis)

    def deriv(self, x, d: int):
        return planar_deriv(x, self.c.coef, self.n, d, self._roll_fn)

    def first(self, d: int):
        """Lanes of the first element along element axis d."""
        return self.c.lmask[2 * d] > 0.5

    def last(self, d: int):
        """Lanes of the last element along element axis d."""
        return self.c.lmask[2 * d + 1] > 0.5

    def next_elem(self, x, d: int):
        """x of the +1 neighbour along element axis d (periodic)."""
        k, t = self.k[d], self.t[d]
        return jnp.where(self.last(d), self.roll(x, (k - 1) * t, 1),
                         self.roll(x, -t, 1))

    def prev_elem(self, x, d: int):
        """x of the -1 neighbour along element axis d (periodic)."""
        k, t = self.k[d], self.t[d]
        return jnp.where(self.first(d), self.roll(x, -(k - 1) * t, 1),
                         self.roll(x, t, 1))

    def lo_of_next(self, x, d: int):
        """On hi face rows: the next element's lo face trace of x."""
        return self.next_elem(
            self.roll(x, (self.n - 1) * self.n ** (2 - d), 0), d)

    def hi_of_prev(self, x, d: int):
        """On lo face rows: x on the previous element's hi face rows."""
        return self.prev_elem(
            self.roll(x, -(self.n - 1) * self.n ** (2 - d), 0), d)

    def lift(self, vol, jump_right, jump_left, d: int,
             inv_w_end: tuple[float, float]):
        """Surface lift: volume term plus the face corrections on the lo /
        hi face rows of node axis d."""
        inv_w0, inv_wn = inv_w_end
        lo = self.c.rmask[2 * d] > 0.5
        hi = self.c.rmask[2 * d + 1] > 0.5
        return jnp.where(lo, vol + (-inv_w0 * jump_left),
                         jnp.where(hi, vol + inv_wn * jump_right, vol))

    def env_mean(self, x, n_elem_total: int):
        """Whole-box quadrature mean per environment, on every lane."""
        col = jnp.sum(self.c.wq[...] * x, axis=0, keepdims=True)
        out = jnp.zeros_like(col)
        for j in range(self.c.emask.shape[0]):
            mine = self.c.emask[j] > 0.5
            tot = jnp.sum(jnp.where(mine, col, 0.0), axis=1, keepdims=True)
            out = jnp.where(mine, tot, out)
        return out / n_elem_total


def _primitives(u):
    rho = u[0]
    vel = [u[1 + i] / rho for i in range(3)]
    kinetic = 0.5 * rho * (vel[0] * vel[0] + vel[1] * vel[1]
                           + vel[2] * vel[2])
    p = (_GAMMA - 1.0) * (u[4] - kinetic)
    temp = p / (rho * _R_GAS)
    return rho, vel, p, temp


def _mom_flux(base, per_comp, p, direction: int):
    """Momentum flux components base * per_comp_i, plus p on the flux
    direction's component."""
    cols = []
    for i in range(3):
        c = base * per_comp[i]
        if i == direction:
            c = c + p
        cols.append(c)
    return cols


def _advective_flux(u, direction: int):
    rho, vel, p, _ = _primitives(u)
    vn = vel[direction]
    f_mom = _mom_flux(vn, u[1:4], p, direction)
    return [u[1 + direction], *f_mom, (u[4] + p) * vn]


def _lax_friedrichs(u_l, u_r, direction: int):
    rho_l, vel_l, p_l, _ = _primitives(u_l)
    rho_r, vel_r, p_r, _ = _primitives(u_r)
    c_l = jnp.sqrt(_GAMMA * p_l / rho_l)
    c_r = jnp.sqrt(_GAMMA * p_r / rho_r)
    lam = jnp.maximum(jnp.abs(vel_l[direction]) + c_l,
                      jnp.abs(vel_r[direction]) + c_r)
    f_l = _advective_flux(u_l, direction)
    f_r = _advective_flux(u_r, direction)
    return [0.5 * (a + b) - 0.5 * lam * (r - l)
            for a, b, l, r in zip(f_l, f_r, u_l, u_r)]


def _flux_differencing(prim, geo: _Planar, direction: int):
    """Split-form volume integral 2 sum_m D[i, m] F#(q_i, q_m) with the
    Kennedy-Gruber two-point flux (all-arithmetic-mean;
    cfd/equations.kennedy_gruber_flux inlined)."""
    rho, vel, p, e = prim
    s = geo.n ** (2 - direction)
    acc = [None] * 5
    for j, o in enumerate(range(-(geo.n - 1), geo.n)):
        def partner(q):
            return geo.roll(q, -o * s, 0)

        rho_m = 0.5 * (rho + partner(rho))
        vel_m = [0.5 * (v + partner(v)) for v in vel]
        p_m = 0.5 * (p + partner(p))
        e_m = 0.5 * (e + partner(e))
        vn = vel_m[direction]
        f_rho = rho_m * vn
        f_mom = _mom_flux(f_rho, vel_m, p_m, direction)
        f_e = f_rho * e_m + p_m * vn
        c = geo.c.coef[direction, j]
        for ch, f in enumerate([f_rho, *f_mom, f_e]):
            acc[ch] = c * f if acc[ch] is None else acc[ch] + c * f
    return [2.0 * a for a in acc]


def _viscous_flux(u, grad, nu_t, direction: int, mu: float,
                  prandtl: float, prandtl_turb: float):
    """grad[c][d] = d q_c / d x_d for q = (v_x, v_y, v_z, T)."""
    rho, vel, _, _ = _primitives(u)
    div_v = grad[0][0] + grad[1][1] + grad[2][2]
    mu_eff = mu + rho * nu_t
    third = (2.0 / 3.0) * mu_eff * div_v
    # column d of tau_ij = 2 mu_eff S_ij - (2/3) mu_eff div(v) delta_ij —
    # only the flux direction's column is needed, so no (3,3) tensor forms
    tau_d = []
    for i in range(3):
        s_id = 0.5 * (grad[i][direction] + grad[direction][i])
        c = 2.0 * mu_eff * s_id
        if i == direction:
            c = c - third
        tau_d.append(c)
    k_eff = _CP * (mu / prandtl + rho * nu_t / prandtl_turb)
    q_d = -k_eff * grad[3][direction]
    work = tau_d[0] * vel[0] + tau_d[1] * vel[1] + tau_d[2] * vel[2]
    return [jnp.zeros_like(rho), *tau_d, work - q_d]


def _wall_stress(u, scale, geo: _Planar, wc: WallConsts, wall: WallParams):
    """Modelled viscous wall flux (tau u_x / |u_par|, tau u_z / |u_par|)
    of each lane's y-face columns, on the y-lo and y-hi face rows (zero on
    the other rows): tau = scale * rho u_tau^2 from Reichardt's law at the
    y-quadrature mean (rho, rho u_x, rho u_z) of the column.

    The column means land on the y-lo face rows (i0, 0, i2); those n^2
    rows are gathered into the leading rows, so the inversion's `iters`
    rounds run on ceil8(n^2) rows, not n^3, and scattered back.  scale:
    the (1, L) row of per-lane wall-stress scales."""
    n = geo.n
    p_rows, lanes = u[0].shape
    m = min(p_rows, -(-n * n // 8) * 8)

    def column_mean(x):   # valid on the y-lo face rows
        acc = None
        for j, w in enumerate(wall.w_half):
            term = w * geo.roll(x, -j * n, 0)
            acc = term if acc is None else acc + term
        return acc

    def gather(x):
        acc = x
        for i0 in range(1, n):
            acc = jnp.where(wc.gather[i0] > 0.5,
                            geo.roll(x, -i0 * (n * n - n), 0), acc)
        return acc[:m]

    def scatter(c):
        if m < p_rows:
            c = jnp.concatenate(
                [c, jnp.zeros((p_rows - m, lanes), c.dtype)], axis=0)
        lo = jnp.where(wc.scatter[0] > 0.5, c, 0.0)
        for i0 in range(1, n):
            lo = jnp.where(wc.scatter[i0] > 0.5,
                           geo.roll(c, i0 * (n * n - n), 0), lo)
        hi = geo.roll(lo, (n - 1) * n, 0)
        return jnp.where(geo.c.rmask[2] > 0.5, lo, hi)

    rho_m = gather(column_mean(u[0]))
    ux_m = gather(column_mean(u[1])) / rho_m
    uz_m = gather(column_mean(u[3])) / rho_m
    u_par = jnp.sqrt(ux_m * ux_m + uz_m * uz_m + 1e-12)
    tau = scale * reichardt_stress(u_par, rho_m, y_m=wall.y_m, nu=wall.nu,
                                   kappa=wall.kappa, iters=wall.iters)
    return scatter(tau * ux_m / u_par), scatter(tau * uz_m / u_par)


def navier_stokes_rhs_planar(
    u, cs, consts: PlanarConsts, *, n: int, k,
    inv_w_end: tuple[float, float], jac, delta: float, mu: float,
    prandtl: float, prandtl_turb: float, forcing_a0: float = 0.0,
    k_tke: float = 0.0, wall: WallParams | None = None,
    wall_consts: WallConsts | None = None, roll=jnp.roll,
) -> list:
    """The fused RHS on planar operands — the body of kernels/rhs.py.

    u: 5 conservative planes (indexable, e.g. a (5, P, L) array or a VMEM
    ref); cs: the (P, L) Smagorinsky coefficient plane, or with `wall` the
    (1, L) row of per-lane wall-stress scales; consts for this block
    (`planar_consts`).  k: K (periodic K^3 mesh) or (Kx, Ky, Kz); jac: one
    scalar or one per direction.  `wall` (static) selects the
    wall-modelled channel variant, with `wall_consts`.  `roll` is
    `jnp.roll` under XLA and `kernel_roll` in a kernel body.  Returns the
    5 float32 RHS planes.

    Pipeline (the op order of cfd/solver.navier_stokes_rhs and
    cfd/channel.channel_rhs): primitive decode -> BR1 gradient of (v, T)
    -> Smagorinsky nu_t -> per-direction split-form Kennedy-Gruber volume
    + LLF surface + BR1 viscous divergence -> forcing (Lundgren's
    whole-box means, or the wall variant's constant pressure gradient).
    """
    f32 = jnp.float32
    geo = _Planar(consts, n, k, roll)
    jacs = tuple(jac) if isinstance(jac, (tuple, list)) else (jac,) * 3
    u = [u[c].astype(f32) for c in range(5)]
    cs = cs[...].astype(f32)

    rho, vel, p, temp = _primitives(u)
    e_spec = u[4] / rho
    prim = (rho, vel, p, e_spec)
    q_prim = [*vel, temp]

    # BR1 gradient of (v, T): central interface averages, periodic wrap;
    # the wall variant's y walls take the interior trace with v_y = 0
    grad = [[None] * 3 for _ in range(4)]
    for d in range(3):
        for c, q in enumerate(q_prim):
            vol = geo.deriv(q, d)
            q_star_right = 0.5 * (q + geo.lo_of_next(q, d))
            if wall is not None and d == 1:
                q_wall = 0.0 if c == 1 else q
                q_star_right = jnp.where(geo.last(d), q_wall, q_star_right)
                q_star_left = jnp.where(geo.first(d), q_wall,
                                        geo.hi_of_prev(q_star_right, d))
            else:
                q_star_left = geo.hi_of_prev(q_star_right, d)
            g = geo.lift(vol, q_star_right - q, q_star_left - q, d,
                         inv_w_end)
            grad[c][d] = g * jacs[d]

    # Smagorinsky eddy viscosity (paper Eq. 3)
    s2 = None
    for i in range(3):
        for j in range(3):
            s_ij = 0.5 * (grad[i][j] + grad[j][i])
            s2 = s_ij * s_ij if s2 is None else s2 + s_ij * s_ij
    s_mag = jnp.sqrt(2.0 * s2 + 1e-30)
    nu_t = (cs * delta if wall is None else wall.cs * delta) ** 2 * s_mag

    if wall is not None:
        # +y wall fluxes (advective - viscous): [0, -/+V_x, p, -/+V_z, 0]
        # at the bottom / top wall (the stress's sign flips with the side)
        v_x, v_z = _wall_stress(u, cs, geo, wall_consts, wall)
        g_top = [0.0, v_x, p, v_z, 0.0]
        g_bot = [0.0, -v_x, p, -v_z, 0.0]

    rhs = None
    for d in range(3):
        # advective: split-form volume + LLF surface
        vol_adv = _flux_differencing(prim, geo, d)
        f_adv_nodes = _advective_flux(u, d)
        u_right = [geo.lo_of_next(x, d) for x in u]
        f_star_adv = _lax_friedrichs(u, u_right, d)
        # viscous: standard derivative volume + central surface
        f_visc = _viscous_flux(u, grad, nu_t, d, mu, prandtl, prandtl_turb)
        div_d = []
        for c in range(5):
            vol_visc = geo.deriv(f_visc[c], d)
            f_star_visc = 0.5 * (f_visc[c] + geo.lo_of_next(f_visc[c], d))
            vol = vol_adv[c] - vol_visc
            f_star = f_star_adv[c] - f_star_visc
            f_nodes = f_adv_nodes[c] - f_visc[c]
            if wall is not None and d == 1:
                f_star = jnp.where(geo.last(d), g_top[c], f_star)
                f_star_left = jnp.where(geo.first(d), g_bot[c],
                                        geo.hi_of_prev(f_star, d))
            else:
                f_star_left = geo.hi_of_prev(f_star, d)
            div_d.append(geo.lift(vol, f_star - f_nodes,
                                  f_star_left - f_nodes, d, inv_w_end)
                         * jacs[d])
        rhs = ([-x for x in div_d] if rhs is None
               else [r - x for r, x in zip(rhs, div_d)])

    if wall is not None:
        # constant streamwise pressure-gradient forcing
        f_mom_x = rho * wall.f_x
        return [rhs[0], rhs[1] + f_mom_x, rhs[2], rhs[3],
                rhs[4] + f_mom_x * vel[0]]

    # Lundgren linear forcing + proportional TKE controller.  Whole meshes
    # sit in the block, so the global quadrature means are computed in-pass.
    n_elem_total = geo.k[0] * geo.k[1] * geo.k[2]
    mom = u[1:4]
    mom_fluct = [m - geo.env_mean(m, n_elem_total) for m in mom]
    ke_density = 0.5 * (mom[0] * vel[0] + mom[1] * vel[1] + mom[2] * vel[2])
    k_now = geo.env_mean(ke_density, n_elem_total)
    a_eff = forcing_a0 * jnp.clip(
        k_tke / jnp.maximum(k_now, 0.1 * k_tke), 0.0, 3.0)
    f_mom = [a_eff * m for m in mom_fluct]
    f_e = f_mom[0] * vel[0] + f_mom[1] * vel[1] + f_mom[2] * vel[2]
    return [rhs[0], *(r + f for r, f in zip(rhs[1:4], f_mom)), rhs[4] + f_e]


def navier_stokes_rhs_fused(
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
) -> jax.Array:
    """One fused periodic-HIT Navier-Stokes RHS evaluation — the
    mega-kernel oracle (kernels/rhs.py runs `navier_stokes_rhs_planar` on
    each VMEM block; this runs it on the whole batch at once).

    u: (..., K, K, K, n, n, n, 5) conservative state (any leading batch);
    cs_nodes: per-node Smagorinsky coefficient, shaped like u[..., 0];
    d_matrix: (n, n) Lagrange derivative matrix; w: (n,) GLL quadrature
    weights.  Scalars: `inv_w_end` endpoint inverse weights, `jac` the
    reference-to-physical scaling, `delta` the LES filter width, gas
    parameters and the Lundgren forcing controller (forcing_a0, k_tke).
    All math in float32; the result is cast to u.dtype (bf16 in/out for
    the mixed-precision rollout).
    """
    mesh = u.shape[-7:]
    k, n = mesh[0], mesh[3]
    ub = u.reshape((-1,) + mesh)
    csb = cs_nodes.reshape((-1,) + mesh[:-1] + (1,))
    consts = planar_consts(d_matrix, w, n, k, ub.shape[0])
    planes = navier_stokes_rhs_planar(
        to_planar(ub), to_planar(csb)[0], consts, n=n, k=k,
        inv_w_end=inv_w_end, jac=jac, delta=delta, mu=mu, prandtl=prandtl,
        prandtl_turb=prandtl_turb, forcing_a0=forcing_a0, k_tke=k_tke)
    out = from_planar(jnp.stack(planes), mesh)
    return out.astype(u.dtype).reshape(u.shape)


# --- flash attention ---------------------------------------------------------
def mha(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    scale: float | None = None,
) -> jax.Array:
    """Naive full-materialization GQA attention — the flash kernel's oracle.

    q: (B, Hq, Sq, D);  k, v: (B, Hkv, Skv, D) with Hq % Hkv == 0.
    `window`: sliding-window size w — position i attends to [i-w+1, i]
    (count includes self), applied on ABSOLUTE positions assuming q occupies
    the last Sq positions of the Skv-long context (decode convention).
    `softcap`: gemma-2 logit soft-capping cap*tanh(x/cap).
    """
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    group = hq // hkv
    scale = (d ** -0.5) if scale is None else scale
    kg = jnp.repeat(k, group, axis=1)
    vg = jnp.repeat(v, group, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        kg.astype(jnp.float32)) * scale
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    q_pos = jnp.arange(sq)[:, None] + (skv - sq)  # absolute q positions
    k_pos = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    weights = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", weights, vg.astype(jnp.float32))
    return out.astype(q.dtype)


def mha_chunked(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    scale: float | None = None,
    block_k: int = 512,
    unroll: bool = False,
) -> jax.Array:
    """Flash-equivalent chunked attention in pure jnp (lax.scan over KV
    blocks, online softmax).  O(Sq * D) memory — the dry-run/TPU-free form
    with the same FLOP count and HBM traffic shape as the Pallas kernel."""
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    group = hq // hkv
    scale = (d ** -0.5) if scale is None else scale
    n_blocks = -(-skv // block_k)
    pad = n_blocks * block_k - skv
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kb = kp.reshape(b, hkv, n_blocks, block_k, d)
    vb = vp.reshape(b, hkv, n_blocks, block_k, d)

    q32 = q.astype(jnp.float32)
    q_pos = jnp.arange(sq) + (skv - sq)

    def body(carry, blk):
        m, l, acc = carry
        k_blk, v_blk, start = blk  # (B, Hkv, bk, D), scalar
        k_blk = jnp.repeat(k_blk, group, axis=1).astype(jnp.float32)
        v_blk = jnp.repeat(v_blk, group, axis=1).astype(jnp.float32)
        logits = jnp.einsum("bhqd,bhkd->bhqk", q32, k_blk) * scale
        if softcap is not None:
            logits = softcap * jnp.tanh(logits / softcap)
        k_pos = start + jnp.arange(block_k)
        mask = k_pos[None, :] < skv
        if causal:
            mask &= k_pos[None, :] <= q_pos[:, None]
        if window is not None:
            mask &= k_pos[None, :] > q_pos[:, None] - window
        logits = jnp.where(mask[None, None], logits, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        # guard: rows with no valid key yet keep m=-inf -> exp(0)=1 row sums
        alpha = jnp.exp(jnp.where(jnp.isinf(m), 0.0, m - m_new))
        p = jnp.exp(logits - m_new[..., None])
        p = jnp.where(mask[None, None], p, 0.0)
        l_new = alpha * l + jnp.sum(p, axis=-1)
        acc_new = alpha[..., None] * acc + jnp.einsum("bhqk,bhkd->bhqd", p, v_blk)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hq, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, hq, sq), jnp.float32)
    acc0 = jnp.zeros((b, hq, sq, d), jnp.float32)
    starts = jnp.arange(n_blocks) * block_k
    if unroll:  # dry-run calibration: no while loop in the HLO
        carry = (m0, l0, acc0)
        for i in range(n_blocks):
            carry, _ = body(carry, (kb[:, :, i], vb[:, :, i], starts[i]))
        m, l, acc = carry
    else:
        (m, l, acc), _ = jax.lax.scan(
            body, (m0, l0, acc0),
            (jnp.moveaxis(kb, 2, 0), jnp.moveaxis(vb, 2, 0), starts),
        )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


# --- gated linear recurrence (RWKV6 / SSM family) -----------------------------
def linear_scan(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,
    u: jax.Array | None = None,
    s0: jax.Array | None = None,
    *,
    decay_before_read: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Exact sequential gated linear recurrence — the chunked kernel's oracle.

    Shapes: q, k, w: (B, T, dk);  v: (B, T, dv);  u: (dk,) or None;
    s0: (B, dk, dv) initial state or None.

    decay_before_read=False  (RWKV6):
        o_t = q_t @ (S_{t-1} + diag(u) k_t v_t^T)
        S_t = diag(w_t) S_{t-1} + k_t v_t^T
    decay_before_read=True   (GLA / Mamba-like):
        S_t = diag(w_t) S_{t-1} + k_t v_t^T
        o_t = q_t @ S_t

    Returns (o: (B, T, dv), s_final: (B, dk, dv)).  All math in f32.
    """
    b, t, dk = q.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    q, k, v, w = (x.astype(f32) for x in (q, k, v, w))
    s0 = jnp.zeros((b, dk, dv), f32) if s0 is None else s0.astype(f32)

    def step(s, xs):
        qt, kt, vt, wt = xs  # (B, dk), (B, dk), (B, dv), (B, dk)
        kv = kt[..., :, None] * vt[..., None, :]  # (B, dk, dv)
        if decay_before_read:
            s_new = wt[..., :, None] * s + kv
            o = jnp.einsum("bk,bkv->bv", qt, s_new)
        else:
            read = s + (u[None, :, None] * kv if u is not None else kv)
            o = jnp.einsum("bk,bkv->bv", qt, read)
            s_new = wt[..., :, None] * s + kv
        return s_new, o

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, w))
    s_final, o = jax.lax.scan(step, s0, xs)
    return jnp.moveaxis(o, 0, 1), s_final


def linear_scan_chunked(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,
    u: jax.Array | None = None,
    s0: jax.Array | None = None,
    *,
    decay_before_read: bool = False,
    chunk: int = 64,
    unroll: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Chunk-parallel form of `linear_scan` in pure jnp (lax.scan over
    chunks, dense intra-chunk math) — the exact algorithm of the Pallas
    kernel, usable on any backend and fully differentiable.  This is the
    implementation the models use for training and the dry-run."""
    b, t, dk = q.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    chunk = min(chunk, t)
    pad = (-t) % chunk
    q, k, v, w = (x.astype(f32) for x in (q, k, v, w))
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
        w = jnp.pad(w, ((0, 0), (0, pad), (0, 0)), constant_values=1.0)
    tp = t + pad
    nc = tp // chunk
    qc, kc, vc, wc = (x.reshape(b, nc, chunk, -1).swapaxes(0, 1)
                      for x in (q, k, v, w))
    s_init = jnp.zeros((b, dk, dv), f32) if s0 is None else s0.astype(f32)
    mask = (jnp.tril(jnp.ones((chunk, chunk), jnp.bool_))
            if decay_before_read
            else jnp.tril(jnp.ones((chunk, chunk), jnp.bool_), k=-1))

    def body(s, xs):
        qb, kb, vb, wb = xs  # (B, C, d*)
        cw = jnp.cumsum(jnp.log(jnp.maximum(wb, 1e-30)), axis=1)
        if decay_before_read:
            q_decay = jnp.exp(cw)
            pair = cw[:, :, None, :] - cw[:, None, :, :]
        else:
            cw_prev = jnp.concatenate([jnp.zeros_like(cw[:, :1]), cw[:, :-1]],
                                      axis=1)
            q_decay = jnp.exp(cw_prev)
            pair = cw_prev[:, :, None, :] - cw[:, None, :, :]
        pair = jnp.where(mask[None, :, :, None], pair, -jnp.inf)
        a = jnp.einsum("btd,bsd,btsd->bts", qb, kb, jnp.exp(pair))
        if not decay_before_read:
            diag = jnp.sum(qb * (u[None, None, :] * kb if u is not None else kb),
                           axis=-1)
            a = a + diag[:, :, None] * jnp.eye(chunk, dtype=f32)[None]
        o = jnp.einsum("bts,bsv->btv", a, vb) + jnp.einsum(
            "btk,bkv->btv", qb * q_decay, s)
        k_decay = jnp.exp(cw[:, -1:, :] - cw)
        s_new = jnp.exp(cw[:, -1])[..., None] * s + jnp.einsum(
            "btk,btv->bkv", kb * k_decay, vb)
        return s_new, o

    if unroll:  # dry-run calibration: no while loop in the HLO
        s_final = s_init
        outs = []
        for i in range(nc):
            s_final, o_i = body(s_final, (qc[i], kc[i], vc[i], wc[i]))
            outs.append(o_i)
        o = jnp.stack(outs, axis=0)
    else:
        s_final, o = jax.lax.scan(body, s_init, (qc, kc, vc, wc))
    o = o.swapaxes(0, 1).reshape(b, tp, dv)
    return o[:, :t], s_final
