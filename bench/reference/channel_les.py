"""Plain float32 reference of the wall-modelled channel LES environment.

Written from the equations, in straightforward `jax.numpy`, and importing
nothing of the system under test.  The plane channel is periodic in x and
z and walled in y; the DGSEM assembly is the HIT reference's
(bench/reference/hit_les.py: split-form Kennedy-Gruber volume fluxes,
local Lax-Friedrichs surfaces, BR1 viscous terms, Carpenter-Kennedy RK),
whose operators this class inherits, with the channel's changes:

  * Kx x Ky x Kz elements of unequal sizes (one jacobian per direction);
  * at the two y walls the gradient takes the interior trace with v_y = 0,
    and the +y numerical flux is the no-penetration pressure flux minus
    the modelled stress: tau_w = a rho u_tau^2 with u_tau from Reichardt's
    law of the wall at the matching height (half the wall element), the
    velocity the y-quadrature mean of the wall element's column, the
    stress along that velocity, a the action of the wall element;
  * a constant Smagorinsky coefficient, and the constant pressure-gradient
    forcing f_x = u_tau^2 / h in place of Lundgren's;
  * observation: the velocities of the wall elements over u_bulk, the top
    wall's mirrored (y nodes reversed, v_y negated); reward 2 exp(-l /
    alpha) - 1 with l the weighted relative L2 error of the x-z mean
    velocity profile against Reichardt's profile.

Layout: a state is one (C, n, n, n, L) array, L = B Kx Ky Kz lanes
(environment, then kx, ky, kz; kz fastest).  `ChannelReference.bank`
makes the initial states: Reichardt's profile at a random bulk factor with
four random-phase perturbation modes, as the repository draws them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .hit_les import (GAMMA, RK_A, RK_B, HITReference, derivative_matrix,
                      gll_nodes_weights)


def reichardt_uplus(y_plus, kappa, xp=jnp):
    """Reichardt's composite law of the wall u+(y+)."""
    return (xp.log1p(kappa * y_plus) / kappa
            + 7.8 * (1.0 - xp.exp(-y_plus / 11.0)
                     - (y_plus / 11.0) * xp.exp(-y_plus / 3.0)))


class ChannelReference(HITReference):
    """One channel configuration: operators and the pure functions of it.

    `cfg` is the configuration file's `physics` dict (n_poly, n_elem,
    lengths, mach, nu, rho0, u_bulk, prandtl, prandtl_turb, cs_sgs, u_tau,
    kappa, wm_iters, cfl, dt_rl, t_end, alpha, a_max, perturb).  The
    action bound a_max is also `cfg["cs_max"]`, the name under which
    bench/reference/training.py reads a scenario's action bound."""

    def __init__(self, cfg: dict):
        self.cfg = dict(cfg, cs_max=cfg["a_max"])
        self.N = int(cfg["n_poly"])
        self.n = self.N + 1
        self.Kx, self.Ky, self.Kz = (int(k) for k in cfg["n_elem"])
        self.cells = self.Kx * self.Ky * self.Kz
        self.E = 2 * self.Kx * self.Kz          # wall elements (the policy's)
        x, w = gll_nodes_weights(self.N)
        self.x_gll, self.w_gll = x, w
        self.w2 = w * 0.5
        self.D = derivative_matrix(x)
        self.inv_w0, self.inv_wn = 1.0 / w[0], 1.0 / w[-1]
        self.dxs = [length / k for length, k in
                    zip(cfg["lengths"], cfg["n_elem"])]
        self.jacs = [2.0 / dx for dx in self.dxs]
        self.delta = float(np.prod(self.dxs)) ** (1.0 / 3.0) / self.n
        self.mu = cfg["rho0"] * cfg["nu"]
        c0 = cfg["u_bulk"] / cfg["mach"]
        self.p0 = cfg["rho0"] * c0**2 / GAMMA
        dt_stable = cfg["cfl"] * min(self.dxs) / ((c0 + 3.0 * cfg["u_bulk"])
                                                  * (2 * self.N + 1))
        self.n_substeps = int(np.ceil(cfg["dt_rl"] / dt_stable))
        self.dt = cfg["dt_rl"] / self.n_substeps
        self.n_actions = int(round(cfg["t_end"] / cfg["dt_rl"]))
        self.f_x = cfg["u_tau"] ** 2 / (0.5 * cfg["lengths"][1])
        self.y_m = 0.5 * self.dxs[1]
        self.u_ref = self._reference_profile()

    # --- layout -------------------------------------------------------------
    def to_planar(self, u: jax.Array) -> jax.Array:
        """(B, Kx, Ky, Kz, n, n, n, C) -> (C, n, n, n, B Kx Ky Kz)."""
        c = u.shape[-1]
        return jnp.transpose(u, (7, 4, 5, 6, 0, 1, 2, 3)).reshape(
            (c, self.n, self.n, self.n, -1))

    def from_planar(self, x: jax.Array) -> jax.Array:
        n = self.n
        x = x.reshape((x.shape[0], n, n, n, -1, self.Kx, self.Ky, self.Kz))
        return jnp.transpose(x, (4, 5, 6, 7, 1, 2, 3, 0))

    def _lanes(self, y):
        """(..., L) -> (..., B, Kx, Ky, Kz)."""
        return y.reshape(y.shape[:-1] + (-1, self.Kx, self.Ky, self.Kz))

    def _elem(self, a: int, size: int):
        """Element index along axis a of each of `size` lanes."""
        k = (self.Kx, self.Ky, self.Kz)[a]
        t = (self.Ky * self.Kz, self.Kz, 1)[a]
        return (jnp.arange(size) // t) % k

    # --- stencil operators on (..., n, n, n, L) arrays ------------------------
    def _lane_shift(self, y, a, step):
        """y of the element `step` (+1 or -1) along element axis a,
        periodic."""
        k = (self.Kx, self.Ky, self.Kz)[a]
        t = (self.Ky * self.Kz, self.Kz, 1)[a]
        elem = self._elem(a, y.shape[-1])
        if step > 0:
            return jnp.where(elem == k - 1, jnp.roll(y, (k - 1) * t, -1),
                             jnp.roll(y, -t, -1))
        return jnp.where(elem == 0, jnp.roll(y, -(k - 1) * t, -1),
                         jnp.roll(y, t, -1))

    def _walls(self, size):
        """(bottom-wall lanes, top-wall lanes) masks."""
        ey = self._elem(1, size)
        return ey == 0, ey == self.Ky - 1

    # --- the wall model ---------------------------------------------------------
    def wall_stress(self, u, scale):
        """(tau u_x / |u_par|, tau u_z / |u_par|) per (x, z) face column of
        every lane, (n, n, L): tau = scale rho u_tau^2 by the damped fixed
        point on Reichardt's law at the column's y-quadrature mean."""
        w2 = jnp.asarray(self.w2, jnp.float32)[None, None, :, None, None]
        mean = jnp.sum(w2 * u[jnp.array([0, 1, 3])], axis=2)   # (3, n, n, L)
        rho, ux, uz = mean[0], mean[1] / mean[0], mean[2] / mean[0]
        up = jnp.sqrt(ux * ux + uz * uz + 1e-12)
        nu, y_m, kappa = self.cfg["nu"], self.y_m, self.cfg["kappa"]
        u_tau = jnp.sqrt(nu * up / y_m + 1e-12)
        for _ in range(int(self.cfg["wm_iters"])):
            u_plus = jnp.maximum(
                reichardt_uplus(y_m * u_tau / nu, kappa), 1e-6)
            u_tau = jnp.sqrt(u_tau * up / u_plus + 1e-14)
        tau = scale * rho * u_tau**2
        return tau * ux / up, tau * uz / up

    # --- physics ------------------------------------------------------------
    def rhs(self, u, scale):
        """Semi-discrete RHS of planar u (5, n, n, n, L) under the per-lane
        wall-stress scale (L,) (read on the wall elements)."""
        cfg = self.cfg
        bottom, top = self._walls(u.shape[-1])
        rho, vel, p, temp = self.primitives(u)
        e = u[4] / rho
        q = jnp.concatenate([vel, temp[None]])
        grads = []
        for d in range(3):   # BR1 gradient with central interface values
            vol = self.deriv(q, d)
            q_l, q_r = self.traces(q, d)
            q_lo = self._face(q, d, 0)
            star_r = 0.5 * (q_l + q_r)
            if d == 1:   # the walls: the interior trace with v_y = 0
                star_r = jnp.where(top, q_l.at[1].set(0.0), star_r)
            star_l = self._lane_shift(star_r, d, -1)
            if d == 1:
                star_l = jnp.where(bottom, q_lo.at[1].set(0.0), star_l)
            g = self.lift(vol, star_r - q_l, star_l - q_lo, d)
            grads.append(g * self.jacs[d])
        grad = jnp.stack(grads, axis=1)          # (4, 3, n, n, n, L)
        gv = grad[0:3]
        s_ij = 0.5 * (gv + jnp.swapaxes(gv, 0, 1))
        s_mag = jnp.sqrt(2.0 * jnp.sum(s_ij * s_ij, axis=(0, 1)) + 1e-30)
        nu_t = (cfg["cs_sgs"] * self.delta) ** 2 * s_mag

        tx, tz = self.wall_stress(u, scale)
        zero = jnp.zeros_like(tx)
        # +y wall flux = advective (pressure only) - viscous (the stress,
        # positive at the bottom wall, negative at the top)
        g_bot = jnp.stack([zero, -tx, self._face(p, 1, 0), -tz, zero])
        g_top = jnp.stack([zero, tx, self._face(p, 1, self.n - 1), tz, zero])

        out = None
        for d in range(3):
            vol_adv = self.flux_differencing(rho, vel, p, e, d)
            f_adv = self.advective_flux(u, d)
            u_l, u_r = self.traces(u, d)
            star_adv = self.lax_friedrichs(u_l, u_r, d)
            f_visc = self.viscous_flux(u, grad, nu_t, d)
            vol_visc = self.deriv(f_visc, d)
            fv_l, fv_r = self.traces(f_visc, d)
            f_star = star_adv - 0.5 * (fv_l + fv_r)
            if d == 1:
                f_star = jnp.where(top, g_top, f_star)
            f_star_left = self._lane_shift(f_star, d, -1)
            if d == 1:
                f_star_left = jnp.where(bottom, g_bot, f_star_left)
            f_nodes = f_adv - f_visc
            div = self.lift(vol_adv - vol_visc,
                            f_star - self._face(f_nodes, d, self.n - 1),
                            f_star_left - self._face(f_nodes, d, 0), d)
            div = div * self.jacs[d]
            out = -div if out is None else out - div

        f_mom_x = rho * self.f_x
        zero = jnp.zeros_like(rho)
        return out + jnp.stack([zero, f_mom_x, zero, zero,
                                f_mom_x * vel[0]])

    def advance(self, u, scale, dtype=jnp.float32):
        """Advance planar u by dt_rl under the per-lane scale (L,); the
        state is carried in `dtype` (float32, or bfloat16 for a control)."""
        dt = jnp.asarray(self.dt, dtype)
        scale = scale.astype(dtype).astype(jnp.float32)

        def substep(u, _):
            du = jnp.zeros_like(u)
            for a, b in zip(RK_A, RK_B):
                r = self.rhs(u.astype(jnp.float32), scale).astype(dtype)
                du = a * du + dt * r
                u = u + b * du
            return u, None

        u, _ = jax.lax.scan(substep, u.astype(dtype), None,
                            length=self.n_substeps)
        return u.astype(jnp.float32)

    # --- environment ---------------------------------------------------------
    def observe(self, u):
        """Planar state -> observations (B, E, n, n, n, 3) / u_bulk: the
        bottom wall's elements (kx, kz order), then the top wall's with the
        y nodes reversed and v_y negated."""
        vel = self._lanes(u[1:4] / u[0])      # (3, n, n, n, B, Kx, Ky, Kz)
        b = vel.shape[4]
        n = self.n

        def wall(ky):
            v = jnp.transpose(vel[..., ky, :], (4, 5, 6, 1, 2, 3, 0))
            return v.reshape((b, self.Kx * self.Kz, n, n, n, 3))

        top = jnp.flip(wall(self.Ky - 1), axis=-3) * jnp.asarray(
            [1.0, -1.0, 1.0], jnp.float32)
        return jnp.concatenate([wall(0), top], axis=1) / self.cfg["u_bulk"]

    def _reference_profile(self) -> np.ndarray:
        """Reichardt's mean velocity at the y nodes, (Ky, n)."""
        cfg = self.cfg
        dy = self.dxs[1]
        y = ((np.arange(self.Ky) + 0.5) * dy)[:, None] \
            + 0.5 * dy * self.x_gll[None, :]
        dist = np.minimum(y, cfg["lengths"][1] - y)
        return (cfg["u_tau"] * reichardt_uplus(
            dist * cfg["u_tau"] / cfg["nu"], cfg["kappa"], xp=np)
        ).astype(np.float32)

    def reward(self, u):
        """2 exp(-l / alpha) - 1 per env, l the weighted relative squared
        L2 error of the x-z mean streamwise velocity profile."""
        w2 = jnp.asarray(self.w2, jnp.float32)
        ux = self._lanes(u[1] / u[0])           # (n, n, n, B, Kx, Ky, Kz)
        prof = jnp.einsum("ijkbxyz,i,k->byj", ux, w2, w2) / (
            self.Kx * self.Kz)
        ref = jnp.asarray(self.u_ref)
        num = jnp.einsum("byj,j->b", (prof - ref) ** 2, w2)
        den = jnp.einsum("yj,j->", ref * ref, w2)
        ell = num / jnp.maximum(den, 1e-12)
        return 2.0 * jnp.exp(-ell / self.cfg["alpha"]) - 1.0

    def lane_scale(self, action):
        """Action (B, E) -> the per-lane wall-stress scale (L,): bottom
        wall elements on ky = 0, top on ky = Ky-1, 1 in between."""
        a = jnp.clip(action, 0.0, self.cfg["a_max"])
        b, kx, kz = a.shape[0], self.Kx, self.Kz
        bot = a[:, :kx * kz].reshape((b, kx, 1, kz))
        top = a[:, kx * kz:].reshape((b, kx, 1, kz))
        mid = jnp.ones((b, kx, self.Ky - 2, kz), a.dtype)
        return jnp.concatenate([bot, mid, top], axis=2).reshape(-1)

    def step(self, u, action, dtype=jnp.float32):
        """One MDP transition of planar u under action (B, E): the next
        state, with the blow-up guard (a non-finite env keeps its state and
        gets the reward floor -1), and the reward."""
        u_next = self.advance(u, self.lane_scale(action), dtype)
        lanes_ok = jnp.all(jnp.isfinite(u_next), axis=(0, 1, 2, 3))
        env_ok = jnp.all(lanes_ok.reshape((-1, self.cells)), axis=-1)
        lane_mask = jnp.repeat(env_ok, self.cells)
        u_next = jnp.where(lane_mask, u_next, u)
        r = jnp.where(env_ok, self.reward(u_next), -1.0)
        return u_next, r

    # --- initial states ------------------------------------------------------
    @functools.partial(jax.jit, static_argnums=(0, 2))
    def bank(self, key, size: int):
        """`size` initial states (size, Kx, Ky, Kz, n, n, n, 5), float32."""
        return jax.vmap(self._initial_state)(jax.random.split(key, size))

    def _coords(self, a: int) -> jax.Array:
        dx = self.dxs[a]
        k = (self.Kx, self.Ky, self.Kz)[a]
        c = ((np.arange(k) + 0.5) * dx)[:, None] + 0.5 * dx * self.x_gll
        return jnp.asarray(c, jnp.float32)

    def _initial_state(self, key):
        cfg = self.cfg
        key, key_amp = jax.random.split(key)
        n, kx, ky, kz = self.n, self.Kx, self.Ky, self.Kz
        shape = (kx, ky, kz, n, n, n)
        cx, cy, cz = (self._coords(a) for a in range(3))
        x = jnp.broadcast_to(cx[:, None, None, :, None, None], shape)
        y = jnp.broadcast_to(cy[None, :, None, None, :, None], shape)
        z = jnp.broadcast_to(cz[None, None, :, None, None, :], shape)
        u_ref = jnp.asarray(self.u_ref)
        bulk = jax.random.uniform(key_amp, (), jnp.float32, 0.75, 1.25)
        ux = jnp.broadcast_to(u_ref[None, :, None, None, :, None],
                              shape) * bulk
        uy = jnp.zeros(shape, jnp.float32)
        uz = jnp.zeros(shape, jnp.float32)
        envelope = jnp.sin(np.pi * y / cfg["lengths"][1])
        lx, _, lz = cfg["lengths"]
        phases = jax.random.uniform(key, (4, 3), jnp.float32, 0.0,
                                    2.0 * np.pi)
        amp = cfg["perturb"] * cfg["u_bulk"]
        for m, (mx, mz) in enumerate(((1, 1), (1, 2), (2, 1), (2, 2))):
            kx_, kz_ = 2.0 * np.pi * mx / lx, 2.0 * np.pi * mz / lz
            ux = ux + amp * envelope * jnp.sin(kx_ * x + phases[m, 0]) \
                * jnp.cos(kz_ * z)
            uy = uy + amp * envelope * jnp.cos(kx_ * x + phases[m, 1]) \
                * jnp.sin(kz_ * z)
            uz = uz + amp * envelope * jnp.sin(kz_ * z + phases[m, 2]) \
                * jnp.cos(kx_ * x)
        rho = jnp.full(shape, cfg["rho0"], jnp.float32)
        vel = jnp.stack([ux, uy, uz], axis=-1)
        e_tot = self.p0 / (GAMMA - 1.0) + 0.5 * rho * jnp.sum(vel * vel, -1)
        return jnp.concatenate([rho[..., None], rho[..., None] * vel,
                                e_tot[..., None]], axis=-1)
