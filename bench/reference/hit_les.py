"""Plain float32 reference of the HIT-LES environment (Kurz et al. 2022).

Written from the equations, in straightforward `jax.numpy`, and importing
nothing of the system under test.  It follows the staged DGSEM assembly of
the repository's solver: split-form Kennedy-Gruber volume fluxes, local
Lax-Friedrichs surface fluxes, BR1 viscous terms with a Smagorinsky eddy
viscosity, Lundgren linear forcing with a proportional TKE controller, and
the Carpenter-Kennedy five-stage low-storage RK.

Layout: a state is one (C, n, n, n, L) array, node axes 1-3 and the lanes
L = B * K^3 environments times elements (element z fastest), so every
array is dense on the chip and elementwise work needs no transposes.  The
derivative and interpolation contractions are explicit float32 sums, so no
matrix unit precision is involved.

`HITReference.bank` makes the initial-state bank: divergence-free Gaussian
velocity fields with the von Karman-Pao spectrum (Rogallo sampling),
evaluated exactly at the GLL nodes by Fourier interpolation — a copy of the
generator the repository uses, so the benchmark owns its input data.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

GAMMA = 1.4
R_GAS = 1.0
CP = GAMMA * R_GAS / (GAMMA - 1.0)

# Carpenter & Kennedy (1994) five-stage fourth-order low-storage RK
RK_A = (0.0,
        -567301805773.0 / 1357537059087.0,
        -2404267990393.0 / 2016746695238.0,
        -3550918686646.0 / 2091501179385.0,
        -1275806237668.0 / 842570457699.0)
RK_B = (1432997174477.0 / 9575080441755.0,
        5161836677717.0 / 13612068292357.0,
        1720146321549.0 / 2090206949498.0,
        3134564353537.0 / 4481467310338.0,
        2277821191437.0 / 14882151754819.0)


# --- GLL operators (numpy, float64) ------------------------------------------
def _legendre(n: int, x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    p0, p1 = np.ones_like(x), x.copy()
    if n == 0:
        return p0, np.zeros_like(x)
    for k in range(2, n + 1):
        p0, p1 = p1, ((2 * k - 1) * x * p1 - (k - 1) * p0) / k
    return p1, n * (x * p1 - p0) / (x**2 - 1.0 + 1e-300)


def gll_nodes_weights(n_poly: int) -> tuple[np.ndarray, np.ndarray]:
    """Nodes (roots of (1 - x^2) P'_N) and weights 2 / (N (N+1) P_N^2)."""
    x = -np.cos(np.pi * np.arange(n_poly + 1) / n_poly)
    for _ in range(100):
        p, dp = _legendre(n_poly, x)
        dx = (1.0 - x**2) * dp / (-n_poly * (n_poly + 1) * p)
        x = x - dx
        if np.max(np.abs(dx)) < 1e-15:
            break
    x[0], x[-1] = -1.0, 1.0
    x = np.sort(x)
    p, _ = _legendre(n_poly, x)
    return x, 2.0 / (n_poly * (n_poly + 1) * p**2)


def _bary(x: np.ndarray) -> np.ndarray:
    return np.array([1.0 / np.prod([x[j] - x[i] for i in range(len(x))
                                    if i != j]) for j in range(len(x))])


def derivative_matrix(x: np.ndarray) -> np.ndarray:
    """D[i, j] = l_j'(x_i) of the Lagrange basis on nodes x."""
    wb, n = _bary(x), len(x)
    d = np.zeros((n, n))
    for i in range(n):
        for j in range(n):
            if i != j:
                d[i, j] = wb[j] / wb[i] / (x[i] - x[j])
        d[i, i] = -np.sum(d[i])
    return d


def interpolation_matrix(x_from: np.ndarray, x_to: np.ndarray) -> np.ndarray:
    """V[i, j] = l_j(x_to[i]) (barycentric form)."""
    wb = _bary(x_from)
    v = np.zeros((len(x_to), len(x_from)))
    for i, xt in enumerate(x_to):
        diff = xt - x_from
        hit = np.flatnonzero(np.abs(diff) < 1e-14)
        if hit.size:
            v[i, hit[0]] = 1.0
        else:
            t = wb / diff
            v[i] = t / np.sum(t)
    return v


def shell_bins(n_grid: int) -> tuple[np.ndarray, int, np.ndarray]:
    """Integer shell |k| of every rfft mode, shell count, and the weight 2
    of the interior kz planes that rfft stores once."""
    k1 = np.fft.fftfreq(n_grid, d=1.0 / n_grid)
    kr = np.fft.rfftfreq(n_grid, d=1.0 / n_grid)
    kx, ky, kz = np.meshgrid(k1, k1, kr, indexing="ij")
    shells = np.rint(np.sqrt(kx**2 + ky**2 + kz**2)).astype(np.int32)
    weight = np.where((kz == 0) | (2 * kz == n_grid), 1.0, 2.0)
    return shells, int(shells.max()) + 1, weight


def vkp_spectrum(k: np.ndarray, u_rms: float, k_peak: float,
                 k_eta: float) -> np.ndarray:
    """von Karman-Pao spectrum normalized to the TKE 1.5 u_rms^2."""
    k = np.asarray(k, np.float64)
    with np.errstate(divide="ignore", invalid="ignore"):
        s = (k / k_peak) ** 4 / (1.0 + (k / k_peak) ** 2) ** (17.0 / 6.0)
        s = s * np.exp(-2.0 * (k / k_eta) ** 2)
    s = np.where(k > 0, s, 0.0)
    return s * (1.5 * u_rms**2 / max(np.sum(s), 1e-300))


class HITReference:
    """One HIT-LES configuration: operators and the pure functions of it.

    `cfg` is the configuration file's dict (n_poly, n_elem, length, mach,
    nu, rho0, u_rms, prandtl, prandtl_turb, forcing_a0, cfl, dt_rl, t_end,
    k_max, alpha, cs_max, k_peak, k_eta)."""

    def __init__(self, cfg: dict):
        self.cfg = cfg
        self.N, self.K = int(cfg["n_poly"]), int(cfg["n_elem"])
        self.n = self.N + 1
        self.E = self.K**3
        x, w = gll_nodes_weights(self.N)
        self.x_gll, self.w_gll = x, w
        self.D = derivative_matrix(x)
        self.inv_w0, self.inv_wn = 1.0 / w[0], 1.0 / w[-1]
        self.dx = cfg["length"] / self.K
        self.jac = 2.0 / self.dx
        self.delta = self.dx / self.n
        self.mu = cfg["rho0"] * cfg["nu"]
        self.k_tke = 1.5 * cfg["u_rms"] ** 2
        c0 = cfg["u_rms"] / cfg["mach"]
        self.p0 = cfg["rho0"] * c0**2 / GAMMA
        dt_stable = cfg["cfl"] * self.dx / ((c0 + 3.0 * cfg["u_rms"])
                                            * (2 * self.N + 1))
        self.n_substeps = int(np.ceil(cfg["dt_rl"] / dt_stable))
        self.dt = cfg["dt_rl"] / self.n_substeps
        self.n_actions = int(round(cfg["t_end"] / cfg["dt_rl"]))
        self.n_grid = self.K * self.n
        self.V = interpolation_matrix(
            x, -1.0 + (2.0 * np.arange(self.n) + 1.0) / self.n)
        shells, self.n_shells, self.shell_weight = shell_bins(self.n_grid)
        self.shells = shells
        self.e_dns = vkp_spectrum(np.arange(self.n_shells), cfg["u_rms"],
                                  cfg["k_peak"], cfg["k_eta"])
        w2 = w * 0.5
        self.wq = (w2[:, None, None] * w2[None, :, None]
                   * w2[None, None, :])  # unit-mass node weights

    # --- layout -------------------------------------------------------------
    def to_planar(self, u: jax.Array) -> jax.Array:
        """(B, K, K, K, n, n, n, C) -> (C, n, n, n, B K^3)."""
        c = u.shape[-1]
        return jnp.transpose(u, (7, 4, 5, 6, 0, 1, 2, 3)).reshape(
            (c, self.n, self.n, self.n, -1))

    def from_planar(self, x: jax.Array) -> jax.Array:
        """Inverse of `to_planar`."""
        k, n = self.K, self.n
        x = x.reshape((x.shape[0], n, n, n, -1, k, k, k))
        return jnp.transpose(x, (4, 5, 6, 7, 1, 2, 3, 0))

    # --- stencil operators on (n, n, n, L) arrays ----------------------------
    def _contract(self, mat: np.ndarray, x: jax.Array, a: int) -> jax.Array:
        """out[.., i, ..] = sum_m mat[i, m] x[.., m, ..] along node axis a
        (axis a of the (n, n, n, L) block), as a float32 sum."""
        ax = x.ndim - 4 + a
        shape = [1] * (x.ndim + 1)
        shape[ax], shape[ax + 1] = mat.shape
        m = jnp.asarray(mat.reshape(shape), jnp.float32)
        return jnp.sum(m * jnp.expand_dims(x, ax), axis=ax + 1)

    def deriv(self, x, a):
        return self._contract(self.D, x, a)

    def _face(self, x, a, i):
        return jax.lax.index_in_dim(x, i, x.ndim - 4 + a, keepdims=False)

    def _lane_shift(self, y, a, step):
        """y of the element `step` (+1 or -1) along element axis a,
        periodic, on lanes ordered (b, kx, ky, kz)."""
        k = self.K
        t = k ** (2 - a)
        elem = (jnp.arange(y.shape[-1]) // t) % k
        if step > 0:
            return jnp.where(elem == k - 1, jnp.roll(y, (k - 1) * t, -1),
                             jnp.roll(y, -t, -1))
        return jnp.where(elem == 0, jnp.roll(y, -(k - 1) * t, -1),
                         jnp.roll(y, t, -1))

    def traces(self, x, a):
        """(state on the hi face of e, state on the lo face of e+1)."""
        return self._face(x, a, self.n - 1), self._lane_shift(
            self._face(x, a, 0), a, +1)

    def lift(self, vol, jump_right, jump_left, a):
        ax = vol.ndim - 4 + a
        idx_hi = (slice(None),) * ax + (self.n - 1,)
        idx_lo = (slice(None),) * ax + (0,)
        vol = vol.at[idx_hi].add(self.inv_wn * jump_right)
        return vol.at[idx_lo].add(-self.inv_w0 * jump_left)

    def box_mean(self, x):
        """Whole-box quadrature mean per environment, broadcast to lanes."""
        col = jnp.sum(jnp.asarray(self.wq[..., None], jnp.float32) * x,
                      axis=(-4, -3, -2))
        per_env = col.reshape(col.shape[:-1] + (-1, self.E)).sum(-1) / self.E
        return jnp.repeat(per_env, self.E, axis=-1)[..., None, None, None, :]

    # --- physics -------------------------------------------------------------
    @staticmethod
    def primitives(u):
        rho = u[0]
        vel = u[1:4] / rho
        p = (GAMMA - 1.0) * (u[4] - 0.5 * rho * jnp.sum(vel * vel, axis=0))
        return rho, vel, p, p / (rho * R_GAS)

    @staticmethod
    def advective_flux(u, d):
        rho, vel, p, _ = HITReference.primitives(u)
        vn = vel[d]
        f_mom = u[1:4] * vn
        f_mom = f_mom.at[d].add(p)
        return jnp.concatenate([u[1 + d][None], f_mom,
                                ((u[4] + p) * vn)[None]])

    @staticmethod
    def lax_friedrichs(u_l, u_r, d):
        rho_l, vel_l, p_l, _ = HITReference.primitives(u_l)
        rho_r, vel_r, p_r, _ = HITReference.primitives(u_r)
        lam = jnp.maximum(jnp.abs(vel_l[d]) + jnp.sqrt(GAMMA * p_l / rho_l),
                          jnp.abs(vel_r[d]) + jnp.sqrt(GAMMA * p_r / rho_r))
        return (0.5 * (HITReference.advective_flux(u_l, d)
                       + HITReference.advective_flux(u_r, d))
                - 0.5 * lam * (u_r - u_l))

    def flux_differencing(self, rho, vel, p, e, d):
        """2 sum_j D_ij F#(q_i, q_j) along node axis d, Kennedy-Gruber F#."""
        def pair(q):
            ax = q.ndim - 4 + d
            return jnp.expand_dims(q, ax + 1), jnp.expand_dims(q, ax)

        (rho_a, rho_b), (p_a, p_b), (e_a, e_b) = pair(rho), pair(p), pair(e)
        vel_a, vel_b = pair(vel)
        rho_m, p_m, e_m = 0.5 * (rho_a + rho_b), 0.5 * (p_a + p_b), \
            0.5 * (e_a + e_b)
        vel_m = 0.5 * (vel_a + vel_b)
        vn = vel_m[d]
        f_rho = rho_m * vn
        f_mom = f_rho * vel_m
        f_mom = f_mom.at[d].add(p_m)
        f_e = f_rho * e_m + p_m * vn
        f = jnp.concatenate([f_rho[None], f_mom, f_e[None]])
        ax = f.ndim - 5 + d   # (C, n_i, n_j, ...) after the pair axis
        shape = [1] * f.ndim
        shape[ax], shape[ax + 1] = self.D.shape
        dm = jnp.asarray(self.D.reshape(shape), jnp.float32)
        return 2.0 * jnp.sum(dm * f, axis=ax + 1)

    def viscous_flux(self, u, grad, nu_t, d):
        """grad (4, 3, n, n, n, L): d(v_x, v_y, v_z, T) / d x_j."""
        cfg = self.cfg
        rho, vel, _, _ = self.primitives(u)
        div_v = grad[0, 0] + grad[1, 1] + grad[2, 2]
        mu_eff = self.mu + rho * nu_t
        third = (2.0 / 3.0) * mu_eff * div_v
        tau_d = []
        for i in range(3):
            c = 2.0 * mu_eff * (0.5 * (grad[i, d] + grad[d, i]))
            tau_d.append(c - third if i == d else c)
        tau_d = jnp.stack(tau_d)
        k_eff = CP * (self.mu / cfg["prandtl"]
                      + rho * nu_t / cfg["prandtl_turb"])
        q_d = -k_eff * grad[3, d]
        work = jnp.sum(tau_d * vel, axis=0)
        return jnp.concatenate([jnp.zeros_like(rho)[None], tau_d,
                                (work - q_d)[None]])

    def rhs(self, u, cs):
        """Semi-discrete RHS of planar state u (5, n, n, n, L) with the
        per-node Smagorinsky coefficient cs (n, n, n, L)."""
        rho, vel, p, temp = self.primitives(u)
        e = u[4] / rho
        q = jnp.concatenate([vel, temp[None]])
        grads = []
        for d in range(3):   # BR1 gradient with central interface values
            vol = self.deriv(q, d)
            q_l, q_r = self.traces(q, d)
            star_r = 0.5 * (q_l + q_r)
            star_l = self._lane_shift(star_r, d, -1)
            g = self.lift(vol, star_r - q_l, star_l - self._face(q, d, 0), d)
            grads.append(g * self.jac)
        grad = jnp.stack(grads, axis=1)          # (4, 3, n, n, n, L)
        gv = grad[0:3]
        s_ij = 0.5 * (gv + jnp.swapaxes(gv, 0, 1))
        s_mag = jnp.sqrt(2.0 * jnp.sum(s_ij * s_ij, axis=(0, 1)) + 1e-30)
        nu_t = (cs * self.delta) ** 2 * s_mag

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
            f_nodes = f_adv - f_visc
            f_star_left = self._lane_shift(f_star, d, -1)
            div = self.lift(vol_adv - vol_visc,
                            f_star - self._face(f_nodes, d, self.n - 1),
                            f_star_left - self._face(f_nodes, d, 0), d)
            div = div * self.jac
            out = -div if out is None else out - div

        mom = u[1:4]
        fluct = mom - self.box_mean(mom)
        k_now = self.box_mean(0.5 * jnp.sum(mom * vel, axis=0))
        a_eff = self.cfg["forcing_a0"] * jnp.clip(
            self.k_tke / jnp.maximum(k_now, 0.1 * self.k_tke), 0.0, 3.0)
        f_mom = a_eff * fluct
        f_e = jnp.sum(f_mom * vel, axis=0)
        return out + jnp.concatenate([jnp.zeros_like(rho)[None], f_mom,
                                      f_e[None]])

    def advance(self, u, cs_elem, dtype=jnp.float32):
        """Advance planar u by dt_rl under per-lane C_s (L,); the state is
        carried in `dtype` (float32, or bfloat16 for a control)."""
        cs = jnp.broadcast_to(cs_elem, u.shape[1:]).astype(dtype)
        dt = jnp.asarray(self.dt, dtype)

        def substep(u, _):
            du = jnp.zeros_like(u)
            for a, b in zip(RK_A, RK_B):
                r = self.rhs(u.astype(jnp.float32),
                             cs.astype(jnp.float32)).astype(dtype)
                du = a * du + dt * r
                u = u + b * du
            return u, None

        u, _ = jax.lax.scan(substep, u.astype(dtype), None,
                            length=self.n_substeps)
        return u.astype(jnp.float32)

    # --- environment ---------------------------------------------------------
    def observe(self, u):
        """Planar state -> observations (B, E, n, n, n, 3) / u_rms."""
        vel = u[1:4] / u[0]
        b = vel.shape[-1] // self.E
        obs = jnp.transpose(vel, (4, 1, 2, 3, 0)).reshape(
            (b, self.E, self.n, self.n, self.n, 3))
        return obs / self.cfg["u_rms"]

    def spectrum(self, u):
        """Shell-summed kinetic-energy spectrum per env (B, n_shells)."""
        vel = u[1:4] / u[0]
        for a in range(3):
            vel = self._contract(self.V, vel, a)
        k, n = self.K, self.n
        v = vel.reshape((3, n, n, n, -1, k, k, k))
        v = jnp.transpose(v, (4, 5, 1, 6, 2, 7, 3, 0)).reshape(
            (-1, self.n_grid, self.n_grid, self.n_grid, 3))
        vhat = jnp.fft.rfftn(v, axes=(1, 2, 3)) / self.n_grid**3
        dens = 0.5 * jnp.sum(jnp.abs(vhat) ** 2, axis=-1) * jnp.asarray(
            self.shell_weight, jnp.float32)
        seg = jnp.asarray(self.shells.reshape(-1))
        flat = dens.reshape((dens.shape[0], -1))
        return jax.vmap(lambda f: jax.ops.segment_sum(
            f, seg, num_segments=self.n_shells))(flat)

    def reward(self, u):
        e_les = self.spectrum(u)
        sl = slice(1, int(self.cfg["k_max"]) + 1)
        e_dns = jnp.asarray(self.e_dns[sl], jnp.float32)
        ell = jnp.mean(((e_dns - e_les[:, sl]) / e_dns) ** 2, axis=-1)
        return 2.0 * jnp.exp(-ell / self.cfg["alpha"]) - 1.0

    def step(self, u, action, dtype=jnp.float32):
        """One MDP transition of planar u under action (B, E): the next
        state, with the blow-up guard (a non-finite env keeps its state and
        gets the reward floor -1), and the reward."""
        cs = jnp.clip(action, 0.0, self.cfg["cs_max"]).reshape(-1)
        u_next = self.advance(u, cs, dtype)
        lanes_ok = jnp.all(jnp.isfinite(u_next), axis=(0, 1, 2, 3))
        env_ok = jnp.all(lanes_ok.reshape((-1, self.E)), axis=-1)
        lane_mask = jnp.repeat(env_ok, self.E)
        u_next = jnp.where(lane_mask, u_next, u)
        r = jnp.where(env_ok, self.reward(u_next), -1.0)
        return u_next, r

    # --- initial states ------------------------------------------------------
    @functools.partial(jax.jit, static_argnums=(0, 2))
    def bank(self, key, size: int):
        """`size` initial states (size, K, K, K, n, n, n, 5), float32."""
        return jax.vmap(self._initial_state)(jax.random.split(key, size))

    def _initial_state(self, key):
        g = self.n_grid
        shells, n_shells, weight = self.shells, self.n_shells, \
            self.shell_weight
        noise = jax.random.normal(key, (g, g, g, 3), jnp.float32)
        vhat = jnp.fft.rfftn(noise, axes=(0, 1, 2))
        k1 = np.fft.fftfreq(g, d=1.0 / g)
        kr = np.fft.rfftfreq(g, d=1.0 / g)
        kx, ky, kz = np.meshgrid(k1, k1, kr, indexing="ij")
        kv = jnp.asarray(np.stack([kx, ky, kz], -1), jnp.float32)
        ksq = jnp.sum(kv**2, axis=-1, keepdims=True)
        ksq = jnp.where(ksq == 0, 1.0, ksq)
        nyq = g // 2
        mask = (np.abs(kx) < nyq) & (np.abs(ky) < nyq) & (kz < nyq)
        vhat = vhat * jnp.asarray(mask[..., None], vhat.dtype)
        proj = vhat - kv * jnp.sum(kv * vhat, axis=-1, keepdims=True) / ksq
        dens = (0.5 * jnp.sum(jnp.abs(proj) ** 2, axis=-1)
                * jnp.asarray(weight) / g**6)
        e_now = jax.ops.segment_sum(dens.reshape(-1),
                                    jnp.asarray(shells.reshape(-1)),
                                    num_segments=n_shells)
        target = jnp.asarray(self.e_dns, jnp.float32)
        scale = jnp.where(target > 0, jnp.sqrt(
            target / jnp.maximum(e_now, 1e-30)), 0.0)
        proj = proj * scale[jnp.asarray(shells)][..., None]
        vel = jnp.fft.irfftn(proj, s=(g, g, g), axes=(0, 1, 2))
        # band-limited evaluation at the GLL nodes of every element
        offsets = (np.arange(self.K) + 0.5) * self.dx
        coords = (offsets[:, None] + 0.5 * self.dx * self.x_gll[None]
                  ).reshape(-1)
        kk = np.fft.fftfreq(g, d=1.0 / g)
        emat = jnp.asarray(np.exp(2j * np.pi * np.outer(coords, kk)
                                  / self.cfg["length"]) / g, jnp.complex64)
        f = jnp.fft.fftn(vel, axes=(0, 1, 2))
        for ax in range(3):
            f = jnp.moveaxis(jnp.moveaxis(f, ax, -1) @ emat.T, -1, ax)
        k, n = self.K, self.n
        v = jnp.real(f).reshape((k, n, k, n, k, n, 3))
        v = jnp.transpose(v, (0, 2, 4, 1, 3, 5, 6))
        rho = jnp.full(v.shape[:-1], self.cfg["rho0"], jnp.float32)
        e_tot = self.p0 / (GAMMA - 1.0) + 0.5 * rho * jnp.sum(v * v, -1)
        return jnp.concatenate([rho[..., None], rho[..., None] * v,
                                e_tot[..., None]], axis=-1)
