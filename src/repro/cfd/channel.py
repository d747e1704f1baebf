"""Plane-channel flow with wall-modeled LES — the non-periodic DGSEM scenario.

This is the paper group's follow-up workload (SmartFlow's headline case):
RL-controlled wall modeling in a pressure-gradient-driven channel.  The
domain is periodic in x (streamwise) and z (spanwise) and WALLED in y: the
DGSEM surface exchange along y replaces the periodic wrap with weak-form
wall fluxes built on the `dgsem.set_face`/`dgsem.left_faces` BC abstraction.

Boundary treatment (weak, flux-based — nothing is overwritten in the state):

  * advective wall flux: no-penetration; the +y Euler flux at a wall face
    reduces to a pure pressure flux [0, 0, p, 0, 0] from the interior trace,
  * viscous wall flux: wall-MODELED.  The resolved near-wall gradient is not
    trusted (that is the point of WMLES); instead the tangential stress
    tau_w = rho u_tau^2 comes from inverting Reichardt's law of the wall at
    a matching point inside the wall-adjacent element, and the RL action
    scales it per wall element: tau = a * tau_model, a in [0, a_max].
    Energy work and heat flux vanish at the (no-slip, adiabatic) wall,
  * BR1 gradient wall trace: interior trace with the wall-normal velocity
    zeroed (slip-like) — wall friction enters ONLY through the modeled
    flux, which keeps the under-resolved scheme free of the stiff no-slip
    lift jump,

with everything else (split-form Kennedy-Gruber volume terms, LLF interior
surfaces, BR1 viscous interfaces, Carpenter-Kennedy RK5(4)) identical to the
periodic HIT solver.  With `wall=False` every override is skipped and the
assembly IS the periodic path (tests/test_channel.py pins this against
`solver.navier_stokes_rhs`).

The flow is driven by a constant streamwise pressure-gradient forcing
f_x = u_tau_target^2 / h; the reward compares the x-z-averaged mean-velocity
profile against the Reichardt law-of-the-wall reference profile (the
log-law/DNS stand-in), mirroring the spectral-error reward of the HIT case.

State layout is the shared (..., Kx, Ky, Kz, n, n, n, 5) convention with
ANISOTROPIC element counts and lengths per direction (per-direction
jacobians through the grown `dgsem` operator signatures).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import dgsem, equations, gll
from ..kernels import ref as kref
from ..kernels.ref import reichardt_uplus  # canonical formula (kernel oracle)
from .equations import GasParams
from .solver import kernel_grad_nut, rk_substep


@dataclasses.dataclass(frozen=True)
class ChannelConfig:
    """Static configuration of one wall-modeled channel-flow environment."""

    n_poly: int = 3
    n_elem: tuple[int, int, int] = (3, 4, 3)          # (Kx, Ky, Kz)
    lengths: tuple[float, float, float] = (4.0, 2.0, 2.0)
    # gas / flow
    mach: float = 0.3
    nu: float = 5e-3
    rho0: float = 1.0
    u_bulk: float = 1.0        # velocity scale (obs normalization)
    prandtl: float = 0.72
    prandtl_turb: float = 0.9
    cs_sgs: float = 0.1        # fixed interior Smagorinsky coefficient
    # wall model / forcing
    u_tau: float = 0.12        # target friction velocity; f_x = u_tau^2 / h
    kappa: float = 0.41
    wm_iters: int = 8          # fixed-point iterations inverting the wall law
    wall: bool = True          # False -> fully periodic (BC-reduction tests)
    # time stepping
    cfl: float = 0.35
    dt_rl: float = 0.1
    t_end: float = 2.0
    # reward / action
    alpha: float = 0.2         # reward shape, r = 2 exp(-l/alpha) - 1
    a_max: float = 2.0         # wall-stress scaling bound (1.0 = model as-is)
    # initial-state perturbation amplitude (fraction of u_bulk)
    perturb: float = 0.08
    # Pallas kernels for the gradient, eddy-viscosity and wall-model hot
    # spots.  None = auto (kernels.default_impl(): ON and compiled on TPU,
    # off elsewhere; overridable via REPRO_KERNELS); True/False force the
    # choice (off-TPU forced-on runs in interpret mode — the parity-test
    # configuration).
    use_kernels: bool | None = None
    # Rollout compute precision: "fp32" (default, bit-exact legacy path) or
    # "bf16" (state advanced in bfloat16 inside `advance_rl_interval`;
    # kernel-internal math, observations, reward and the PPO update stay
    # float32).  Same contract as HITConfig.precision; gated by
    # tests/test_precision.py.
    precision: str = "fp32"

    @property
    def n(self) -> int:
        return self.n_poly + 1

    @property
    def compute_dtype(self):
        """Rollout state dtype resolved from `precision` (validated here)."""
        if self.precision not in ("fp32", "bf16"):
            raise ValueError(f"unknown precision: {self.precision!r} "
                             f"(expected 'fp32' or 'bf16')")
        return jnp.bfloat16 if self.precision == "bf16" else jnp.float32

    @property
    def kernels_enabled(self) -> bool:
        """Resolved `use_kernels`: the backend policy unless forced."""
        from ..kernels.policy import resolve_use_kernels

        return resolve_use_kernels(self.use_kernels)

    @property
    def dxs(self) -> tuple[float, float, float]:
        return tuple(l / k for l, k in zip(self.lengths, self.n_elem))

    @property
    def jacs(self) -> tuple[float, float, float]:
        return tuple(2.0 / dx for dx in self.dxs)

    @property
    def half_height(self) -> float:
        return 0.5 * self.lengths[1]

    @property
    def f_x(self) -> float:
        """Constant streamwise forcing balancing the target wall stress."""
        return self.u_tau**2 / self.half_height

    @property
    def gas(self) -> GasParams:
        return GasParams(mu=self.rho0 * self.nu, prandtl=self.prandtl,
                         prandtl_turb=self.prandtl_turb)

    @property
    def sound_speed0(self) -> float:
        return self.u_bulk / self.mach

    @property
    def p0(self) -> float:
        return self.rho0 * self.sound_speed0**2 / equations.GAMMA

    @property
    def delta_filter(self) -> float:
        """LES filter width: geometric-mean node spacing."""
        dx, dy, dz = self.dxs
        return float((dx * dy * dz) ** (1.0 / 3.0)) / self.n

    @property
    def dt(self) -> float:
        """Fixed stable timestep (DG CFL ~ 1/(2N+1)) that divides dt_rl."""
        v_max = self.sound_speed0 + 3.0 * self.u_bulk
        dt_stable = self.cfl * min(self.dxs) / (v_max * (2 * self.n_poly + 1))
        n_sub = int(np.ceil(self.dt_rl / dt_stable))
        return self.dt_rl / n_sub

    @property
    def n_substeps(self) -> int:
        return int(round(self.dt_rl / self.dt))

    @property
    def n_actions(self) -> int:
        return int(round(self.t_end / self.dt_rl))

    @property
    def n_wall_elements(self) -> int:
        """Wall-adjacent elements over BOTH walls: 2 * Kx * Kz."""
        return 2 * self.n_elem[0] * self.n_elem[2]

    @property
    def tau_wall(self) -> float:
        """Target wall shear stress rho u_tau^2 — the classic wall-pressure
        normalization scale (p'_rms ~ 2-3 tau_w in channel flow)."""
        return self.rho0 * self.u_tau**2

    @property
    def t0(self) -> float:
        """Background temperature p0 / (rho0 R) — the fluctuation baseline
        for the near-wall temperature observation."""
        return self.p0 / (self.rho0 * equations.R_GAS)

    @property
    def t_tau(self) -> float:
        """Friction-temperature analog u_tau^2 / cp: the viscous-heating
        temperature scale at an adiabatic wall (the classic T_tau = q_w /
        (rho cp u_tau) degenerates to it when q_w is the frictional
        dissipation tau_w u_tau) — the temperature-channel normalization."""
        return self.u_tau**2 / equations.CP

    @property
    def y_match(self) -> float:
        """Wall-model matching height: the wall element's centroid."""
        return 0.5 * self.dxs[1]

    @property
    def wall_params(self) -> kref.WallParams:
        """Static scalars of the fused wall RHS (kernels/rhs.py)."""
        _, w = gll.gll_nodes_weights(self.n_poly)
        return kref.WallParams(
            y_m=self.y_match, nu=self.nu, kappa=self.kappa,
            iters=self.wm_iters, f_x=self.f_x, cs=self.cs_sgs,
            w_half=tuple(float(x) * 0.5 for x in w))

    def operators(self) -> dict:
        _, w = gll.gll_nodes_weights(self.n_poly)
        return {
            "D": jnp.asarray(gll.lagrange_derivative_matrix(self.n_poly),
                             jnp.float32),
            "inv_w_end": (float(1.0 / w[0]), float(1.0 / w[-1])),
            "w": jnp.asarray(w, jnp.float32),
        }


# --- wall law / reference profile -------------------------------------------
# `reichardt_uplus` lives in kernels/ref.py (it is the wall-model kernel's
# oracle formula) and is re-exported here for the profile/reference users.


def node_coords(cfg: ChannelConfig, direction: int) -> np.ndarray:
    """Physical GLL node coordinates along `direction`, shape (K_d, n)."""
    x_gll, _ = gll.gll_nodes_weights(cfg.n_poly)
    dx = cfg.dxs[direction]
    offsets = (np.arange(cfg.n_elem[direction]) + 0.5) * dx
    return offsets[:, None] + 0.5 * dx * x_gll[None, :]


def reference_profile(cfg: ChannelConfig) -> np.ndarray:
    """Target mean streamwise velocity at the y GLL nodes, (Ky, n).

    Reichardt's law at the target u_tau — the synthetic log-law/DNS stand-in
    (symmetric in the two channel halves by construction).
    """
    y = node_coords(cfg, 1)
    y_dist = np.minimum(y, cfg.lengths[1] - y)
    y_plus = y_dist * cfg.u_tau / cfg.nu
    return (cfg.u_tau * reichardt_uplus(y_plus, cfg.kappa, xp=np)
            ).astype(np.float32)


def mean_velocity_profile(u: jax.Array, cfg: ChannelConfig,
                          ops: dict) -> jax.Array:
    """x-z quadrature average of streamwise velocity: (..., Ky, n)."""
    _, vel, _, _ = equations.conservative_to_primitive(u)
    ux = vel[..., 0]  # (..., Kx, Ky, Kz, ni, nj, nk)
    w = ops["w"] * 0.5
    kx, _, kz = cfg.n_elem
    return jnp.einsum("...abcijk,i,k->...bj", ux, w, w) / (kx * kz)


def profile_error(profile: jax.Array, ref: jax.Array, ops: dict) -> jax.Array:
    """Quadrature-weighted relative squared L2 error of the mean profile."""
    w = ops["w"] * 0.5
    num = jnp.einsum("...bj,j->...", (profile - ref) ** 2, w)
    den = jnp.einsum("bj,j->", ref * ref, w)
    return num / jnp.maximum(den, 1e-12)


# --- initial states ---------------------------------------------------------
def sample_initial_state(key: jax.Array, cfg: ChannelConfig) -> jax.Array:
    """One random state (Kx, Ky, Kz, n, n, n, 5): the reference profile at a
    random bulk deficit/excess (so the wall-stress action has work to do —
    over- or under-stressed walls drive the profile back toward or away from
    the target) plus a few random-phase wall-vanishing perturbation modes
    (enough to trip the nonlinearity; no DNS restart files exist offline)."""
    key, key_amp = jax.random.split(key)
    xs = [jnp.asarray(node_coords(cfg, d), jnp.float32) for d in range(3)]
    kx, ky, kz = cfg.n_elem
    n = cfg.n
    shape = (kx, ky, kz, n, n, n)
    x = jnp.broadcast_to(xs[0][:, None, None, :, None, None], shape)
    y = jnp.broadcast_to(xs[1][None, :, None, None, :, None], shape)
    z = jnp.broadcast_to(xs[2][None, None, :, None, None, :], shape)

    u_ref = jnp.asarray(reference_profile(cfg), jnp.float32)
    bulk_factor = jax.random.uniform(key_amp, (), jnp.float32, 0.75, 1.25)
    ux = jnp.broadcast_to(u_ref[None, :, None, None, :, None], shape)
    ux = ux * bulk_factor
    uy = jnp.zeros(shape, jnp.float32)
    uz = jnp.zeros(shape, jnp.float32)

    # wall-vanishing envelope; modes periodic in x/z
    env = jnp.sin(np.pi * y / cfg.lengths[1])
    lx, _, lz = cfg.lengths
    n_modes = 4
    phases = jax.random.uniform(key, (n_modes, 3), jnp.float32,
                                0.0, 2.0 * np.pi)
    amp = cfg.perturb * cfg.u_bulk
    for m, (mx, mz) in enumerate(((1, 1), (1, 2), (2, 1), (2, 2))):
        cx = 2.0 * np.pi * mx / lx
        cz = 2.0 * np.pi * mz / lz
        ux = ux + amp * env * jnp.sin(cx * x + phases[m, 0]) * jnp.cos(cz * z)
        uy = uy + amp * env * jnp.cos(cx * x + phases[m, 1]) * jnp.sin(cz * z)
        uz = uz + amp * env * jnp.sin(cz * z + phases[m, 2]) * jnp.cos(cx * x)

    rho = jnp.full(shape, cfg.rho0, jnp.float32)
    p = jnp.full(shape, cfg.p0, jnp.float32)
    vel = jnp.stack([ux, uy, uz], axis=-1)
    return equations.primitive_to_conservative(rho, vel, p)


def make_state_bank(key: jax.Array, cfg: ChannelConfig,
                    n_states: int) -> jax.Array:
    keys = jax.random.split(key, n_states)
    return jax.vmap(lambda k: sample_initial_state(k, cfg))(keys)


# --- near-wall observation fields --------------------------------------------
def wall_observation(field: jax.Array, cfg: ChannelConfig, *,
                     flip_sign_channel: int | None = None) -> jax.Array:
    """Extract + mirror the wall-adjacent element layers of a nodal field.

    field: (..., Kx, Ky, Kz, n, n, n, C) per-node quantity.  The ky=0 and
    ky=Ky-1 element layers are selected and the top wall is mirrored (y node
    axis flipped; channel `flip_sign_channel`, if given, negated — e.g. the
    wall-normal velocity) so both walls present the same orientation to a
    shared policy trunk: "away from the wall" is always increasing node
    index.  Returns (..., 2*Kx*Kz, n, n, n, C), bottom wall first.
    """
    ky_axis = field.ndim - 7 + 1  # (..., Kx, Ky, Kz, n, n, n, C)
    bot = jax.lax.index_in_dim(field, 0, ky_axis, keepdims=False)
    top = jax.lax.index_in_dim(field, field.shape[ky_axis] - 1, ky_axis,
                               keepdims=False)
    top = jnp.flip(top, axis=-3)
    if flip_sign_channel is not None:
        top = top.at[..., flip_sign_channel].multiply(-1.0)
    kx, _, kz = cfg.n_elem
    n = cfg.n
    batch = field.shape[: field.ndim - 7]
    shape = batch + (kx * kz, n, n, n, field.shape[-1])
    return jnp.concatenate([bot.reshape(shape), top.reshape(shape)], axis=-5)


def wall_velocity_observation(u: jax.Array, cfg: ChannelConfig) -> jax.Array:
    """Wall-adjacent element velocities, (..., 2*Kx*Kz, n, n, n, 3),
    UN-normalized (the env divides by its declared channel scale)."""
    _, vel, _, _ = equations.conservative_to_primitive(u)
    return wall_observation(vel, cfg, flip_sign_channel=1)


def wall_pressure_observation(u: jax.Array, cfg: ChannelConfig) -> jax.Array:
    """Near-wall static-pressure fluctuation p - p0 at the wall-adjacent
    element nodes, (..., 2*Kx*Kz, n, n, n, 1), UN-normalized (the env
    divides by `cfg.tau_wall`).  Mirrored like the velocity field so both
    walls share one orientation; pressure is a scalar, so no sign flip."""
    _, _, p, _ = equations.conservative_to_primitive(u)
    return wall_observation((p - cfg.p0)[..., None], cfg)


def wall_temperature_observation(u: jax.Array, cfg: ChannelConfig
                                 ) -> jax.Array:
    """Near-wall temperature fluctuation T - T0 at the wall-adjacent element
    nodes, (..., 2*Kx*Kz, n, n, n, 1), UN-normalized (the env divides by
    the friction-temperature scale `cfg.t_tau`).  Mirrored like pressure —
    a scalar field, no sign flip."""
    _, _, _, temp = equations.conservative_to_primitive(u)
    return wall_observation((temp - cfg.t0)[..., None], cfg)


# --- wall model -------------------------------------------------------------
def wall_stress_magnitude(u_par: jax.Array, rho_w: jax.Array, y_m: float,
                          cfg: ChannelConfig) -> jax.Array:
    """tau_w = rho u_tau^2 by inverting u_par/u_tau = u+(y_m u_tau / nu).

    Geometrically-damped fixed point: in the viscous limit (u+ ~ y+) the
    damped map lands on the exact laminar stress mu u_par / y_m in one step,
    and in the log regime it contracts; `wm_iters` iterations unroll into
    the jitted RHS.  With `cfg.kernels_enabled` the whole batched inversion
    runs as one fused Pallas launch (kernels/wall_model.py); the ref path is
    its bit-identical oracle.
    """
    from ..kernels import ops as kops

    return kops.wall_model_tau(
        u_par, jnp.broadcast_to(rho_w, jnp.shape(u_par)), y_m=y_m, nu=cfg.nu,
        kappa=cfg.kappa, iters=cfg.wm_iters,
        impl="kernel" if cfg.kernels_enabled else "ref")


def _wall_slab(arr: jax.Array, side: int) -> jax.Array:
    """Select the wall-adjacent element along y from a y-face array
    (..., Kx, Ky, Kz, n, n, C): side 0 -> ky=0, side 1 -> ky=Ky-1."""
    axis = dgsem.ELEM_AXIS[1] + arr.ndim + 1
    index = 0 if side == 0 else arr.shape[axis] - 1
    return jax.lax.index_in_dim(arr, index, axis, keepdims=False)


def _matching_state(u: jax.Array, cfg: ChannelConfig, ops: dict,
                    side: int) -> tuple[jax.Array, jax.Array, jax.Array]:
    """(rho, u_x, u_z) at the wall-model matching point: the y-quadrature
    mean of the wall-adjacent element, per (x, z) face-node column.
    Shapes (..., Kx, Kz, n, n)."""
    axis = dgsem.ELEM_AXIS[1] + u.ndim
    index = 0 if side == 0 else u.shape[axis] - 1
    ue = jax.lax.index_in_dim(u, index, axis, keepdims=False)
    # (..., Kx, Kz, ni, nj, nk, 5): average the y node axis (-3)
    w = ops["w"] * 0.5
    ue = jnp.einsum("...ijkc,j->...ikc", ue, w)
    rho, vel, _, _ = equations.conservative_to_primitive(ue)
    return rho, vel[..., 0], vel[..., 2]


def wall_fluxes(u: jax.Array, scale_bot: jax.Array, scale_top: jax.Array,
                cfg: ChannelConfig, ops: dict
                ) -> tuple[jax.Array, jax.Array]:
    """Combined (advective - viscous) +y numerical flux at the two wall
    faces, each (..., Kx, Kz, n, n, 5).

    scale_bot/scale_top: RL wall-stress scaling at face nodes,
    (..., Kx, Kz, n, n) — broadcast from the per-wall-element action.
    """
    lo_tr, hi_tr = dgsem._face_slices(u, 1)
    u_wall = (_wall_slab(lo_tr, 0), _wall_slab(hi_tr, 1))
    y_m = cfg.y_match
    out = []
    for side, scale in ((0, scale_bot), (1, scale_top)):
        _, _, p_w, _ = equations.conservative_to_primitive(u_wall[side])
        rho_m, ux_m, uz_m = _matching_state(u, cfg, ops, side)
        u_par = jnp.sqrt(ux_m**2 + uz_m**2 + 1e-12)
        tau = scale * wall_stress_magnitude(u_par, rho_m, y_m, cfg)
        # stress acts along the matching-point tangential direction; the
        # +y-flux component tau_xy is positive at the bottom wall (du/dy>0
        # for flow in +x) and negative at the top — sign s flips per side.
        s = 1.0 if side == 0 else -1.0
        tau_x = s * tau * ux_m / u_par
        tau_z = s * tau * uz_m / u_par
        zero = jnp.zeros_like(p_w)
        # advective: no-penetration pressure flux; viscous: modeled stress,
        # zero wall work (no-slip) and zero heat flux (adiabatic)
        f_adv = jnp.stack([zero, zero, p_w, zero, zero], axis=-1)
        f_visc = jnp.stack([zero, tau_x, zero, tau_z, zero], axis=-1)
        out.append(f_adv - f_visc)
    return out[0], out[1]


# --- RHS / stepping ---------------------------------------------------------
def channel_rhs(u: jax.Array, scale_bot: jax.Array, scale_top: jax.Array,
                cfg: ChannelConfig, ops: dict) -> jax.Array:
    """-div(F_adv - F_visc) + pressure-gradient forcing, with wall BCs in y.

    Identical assembly to `solver.navier_stokes_rhs` (split-form
    Kennedy-Gruber volume terms, LLF surfaces, BR1 viscous interfaces) with
    the y-direction surface exchange routed through the dgsem BC helpers;
    `cfg.wall=False` skips every override and reduces to the periodic path.
    """
    gas = cfg.gas
    d_matrix, inv_w_end = ops["D"], ops["inv_w_end"]

    rho, vel, p, temp = equations.conservative_to_primitive(u)
    e_spec = u[..., 4] / rho
    prim = (rho, vel, p, e_spec)
    q_prim = jnp.concatenate([vel, temp[..., None]], axis=-1)

    bc_grad = None
    if cfg.wall:
        # gradient wall trace: interior trace with v_y zeroed (slip-like);
        # wall friction is injected only through the modeled viscous flux.
        lo_tr, hi_tr = dgsem._face_slices(q_prim, 1)
        q_lo = _wall_slab(lo_tr, 0).at[..., 1].set(0.0)
        q_hi = _wall_slab(hi_tr, 1).at[..., 1].set(0.0)
        bc_grad = (None, (q_lo, q_hi), None)
    cs_nodes = jnp.full(u.shape[:-1], cfg.cs_sgs, u.dtype)
    if cfg.kernels_enabled:
        # fused Pallas hot spots, shared with solver.navier_stokes_rhs: the
        # BC-aware surface lift composes with the kernel volume derivatives
        # through dg_gradient's vol_derivs hook.
        grad_prim, nu_t = kernel_grad_nut(q_prim, cs_nodes, d_matrix,
                                          inv_w_end, cfg.delta_filter,
                                          jac=cfg.jacs, bc=bc_grad)
        grad_v = grad_prim[..., 0:3, :]
    else:
        grad_prim = dgsem.dg_gradient(q_prim, None, d_matrix, inv_w_end,
                                      jac=cfg.jacs, bc=bc_grad)
        grad_v = grad_prim[..., 0:3, :]
        s_mag = equations.strain_magnitude(equations.strain_rate(grad_v))
        nu_t = equations.eddy_viscosity(cs_nodes, cfg.delta_filter, s_mag)

    if cfg.wall:
        g_lo, g_hi = wall_fluxes(u, scale_bot, scale_top, cfg, ops)

    rhs = None
    for d in range(3):
        # --- advective: split-form volume + LLF surface -------------------
        vol_adv = dgsem.flux_differencing(
            prim, equations.kennedy_gruber_flux, d_matrix, d
        )
        f_adv_nodes = equations.advective_flux(u, d)
        u_left, u_right = dgsem.neighbor_traces(u, d)
        f_star_adv = equations.lax_friedrichs_flux(u_left, u_right, d)
        # --- viscous: standard derivative volume + central surface --------
        f_visc = equations.viscous_flux(u, grad_prim, nu_t, gas, d)
        vol_visc = dgsem.deriv_along(f_visc, d_matrix, d)
        fv_left, fv_right = dgsem.neighbor_traces(f_visc, d)
        f_star_visc = 0.5 * (fv_left + fv_right)

        vol = vol_adv - vol_visc
        f_star = f_star_adv - f_star_visc
        f_nodes = f_adv_nodes - f_visc
        lo, hi = dgsem._face_slices(f_nodes, d)
        if d == 1 and cfg.wall:
            # non-periodic y: the wrapped faces are replaced by wall fluxes
            f_star = dgsem.set_face(f_star, d, -1, g_hi)
            f_star_left = dgsem.left_faces(f_star, d, lo_value=g_lo)
        else:
            f_star_left = dgsem.left_faces(f_star, d)  # periodic wrap
        div_d = dgsem.surface_lift(vol, f_star - hi, f_star_left - lo, d,
                                   inv_w_end)
        div_d = div_d * cfg.jacs[d]
        rhs = -div_d if rhs is None else rhs - div_d

    # --- constant streamwise pressure-gradient forcing ----------------------
    f_mom_x = rho * cfg.f_x
    f_e = f_mom_x * vel[..., 0]
    zero = jnp.zeros_like(f_mom_x)
    forcing = jnp.stack([zero, f_mom_x, zero, zero, f_e], axis=-1)
    return rhs + forcing


def wall_scale_elements(scale_bot: jax.Array, scale_top: jax.Array,
                        cfg: ChannelConfig) -> jax.Array:
    """Per-wall-element stress scales (..., Kx, Kz) of both walls ->
    one per element (..., Kx, Ky, Kz): the bottom wall's on ky = 0, the
    top wall's on ky = Ky-1, 1 (unread) in between."""
    ky = cfg.n_elem[1]
    mid = jnp.ones(scale_bot.shape[:-1] + (ky - 2,) + scale_bot.shape[-1:],
                   scale_bot.dtype)
    return jnp.concatenate([scale_bot[..., None, :], mid,
                            scale_top[..., None, :]], axis=-2)


def wall_rhs_scalars(cfg: ChannelConfig, ops: dict) -> dict:
    """The static scalars of the fused wall RHS kernel (kernels/rhs.py)."""
    return dict(inv_w_end=ops["inv_w_end"], jac=cfg.jacs,
                delta=cfg.delta_filter, mu=cfg.gas.mu, prandtl=cfg.prandtl,
                prandtl_turb=cfg.prandtl_turb, wall=cfg.wall_params)


@functools.partial(jax.jit, static_argnames=("cfg",))
def advance_rl_interval(u: jax.Array, scale_bot: jax.Array,
                        scale_top: jax.Array,
                        cfg: ChannelConfig) -> jax.Array:
    """Advance the channel LES by Delta t_RL under fixed wall-stress scaling
    (one MDP transition).  u: (..., Kx,Ky,Kz,n,n,n,5); scale_bot/scale_top:
    per-wall-element scaling (..., Kx, Kz).

    With `cfg.kernels_enabled` (and walls on) the RK carry stays in the
    fused kernel's planar layout for the whole interval, as in
    `solver.advance_rl_interval`: the state and the per-lane wall-stress
    scales are converted once before the substep scan
    (`to_planar_wall_batch`) and back once after it (`from_planar_batch`),
    both under the `rhs.layout` scope, and every RK stage calls the
    `fused_wall_rhs` kernel.  Otherwise `channel_rhs` steps the natural
    layout, the scales broadcast to the wall-face nodes — the oracle.

    With `cfg.precision == "bf16"` the state advances in bfloat16 and is
    cast back to float32 at the boundary (obs/reward/PPO stay float32)."""
    ops = cfg.operators()
    dtype = cfg.compute_dtype
    u = u.astype(dtype)
    scale_bot, scale_top = scale_bot.astype(dtype), scale_top.astype(dtype)
    if dtype != jnp.float32:
        # operator matrices must follow the compute dtype or every DG
        # contraction re-promotes the bf16 carry to f32 mid-loop (JAX002)
        ops = dict(ops, D=ops["D"].astype(dtype), w=ops["w"].astype(dtype))

    planar = cfg.kernels_enabled and cfg.wall
    if planar:
        from ..kernels import ops as kops
        from ..kernels.rhs import from_planar_batch, to_planar_wall_batch

        x, scale_pl, block_e = to_planar_wall_batch(
            u, wall_scale_elements(scale_bot, scale_top, cfg))

        def rhs(x):
            return kops.wall_rhs_planar(
                x, scale_pl, ops["D"], ops["w"], k=cfg.n_elem,
                block_e=block_e, impl="kernel", **wall_rhs_scalars(cfg, ops))
    else:
        n = cfg.n
        to_nodes = lambda s: jnp.broadcast_to(s[..., None, None],
                                              s.shape + (n, n))
        sb, st = to_nodes(scale_bot), to_nodes(scale_top)
        x = u

        def rhs(x):
            return channel_rhs(x, sb, st, cfg, ops)

    def body(x, _):
        return rk_substep(x, rhs, cfg.dt), None

    x, _ = jax.lax.scan(body, x, None, length=cfg.n_substeps)
    if planar:
        x = from_planar_batch(x, u.shape)
    return x.astype(jnp.float32)
