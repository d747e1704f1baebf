"""HIT LES solver: RHS assembly, linear forcing and low-storage RK stepping.

This is the transition function T(s_{t+1} | a_t, s_t) of the paper's MDP:
given the current flow state and the per-element Smagorinsky coefficients
(the RL action), advance the compressible Navier-Stokes LES by Delta t_RL.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from . import dgsem, equations
from .dgsem import DGParams
from .equations import GasParams

# Carpenter & Kennedy (1994) five-stage fourth-order low-storage RK —
# FLEXI's default explicit integrator.
_RK_A = np.array(
    [
        0.0,
        -567301805773.0 / 1357537059087.0,
        -2404267990393.0 / 2016746695238.0,
        -3550918686646.0 / 2091501179385.0,
        -1275806237668.0 / 842570457699.0,
    ]
)
_RK_B = np.array(
    [
        1432997174477.0 / 9575080441755.0,
        5161836677717.0 / 13612068292357.0,
        1720146321549.0 / 2090206949498.0,
        3134564353537.0 / 4481467310338.0,
        2277821191437.0 / 14882151754819.0,
    ]
)


@dataclasses.dataclass(frozen=True)
class HITConfig:
    """Static configuration of one HIT LES environment (paper Table 1)."""

    n_poly: int = 5
    n_elem: int = 4
    length: float = 2.0 * np.pi
    # gas / flow
    mach: float = 0.3
    nu: float = 1.8e-3
    rho0: float = 1.0
    u_rms: float = 1.0
    prandtl: float = 0.72
    prandtl_turb: float = 0.9
    # forcing (Lundgren linear forcing + TKE proportional controller)
    forcing_a0: float = 0.3
    # time stepping
    cfl: float = 0.35
    dt_rl: float = 0.1
    t_end: float = 5.0
    # reward (paper Table 1)
    k_max: int = 9
    alpha: float = 0.4
    cs_max: float = 0.5
    # Pallas kernels: with kernels enabled the WHOLE RHS evaluation runs as
    # one fused mega-kernel launch (kernels/rhs.py — derivative, fluxes,
    # eddy viscosity, divergence and forcing with intermediates in VMEM).
    # None = auto (kernels.default_impl(): ON and compiled on TPU, off
    # elsewhere; overridable via REPRO_KERNELS); True/False force the choice
    # (off-TPU forced-on runs in interpret mode — the parity-test
    # configuration).
    use_kernels: bool | None = None
    # Rollout compute precision.  "fp32" (default) is the bit-exact legacy
    # path.  "bf16" advances the state in bfloat16 inside
    # `advance_rl_interval` — the HBM-resident state, RK accumulator and RHS
    # inputs/outputs drop to 16 bits (kernel-internal math stays float32)
    # while observations, reward reduction and the PPO update remain
    # float32.  Opt-in via e.g. `envs.make("hit_les_24dof",
    # precision="bf16")`; gated by the training-curve-equivalence test in
    # tests/test_precision.py.
    precision: str = "fp32"
    # synthetic DNS target spectrum (von Karman-Pao)
    k_peak: float = 4.0
    k_eta: float = 48.0

    @property
    def dg(self) -> DGParams:
        return DGParams(self.n_poly, self.n_elem, self.length)

    @property
    def kernels_enabled(self) -> bool:
        """Resolved `use_kernels`: the backend policy unless forced."""
        from ..kernels.policy import resolve_use_kernels

        return resolve_use_kernels(self.use_kernels)

    @property
    def compute_dtype(self):
        """Rollout state dtype resolved from `precision` (validated here)."""
        if self.precision not in ("fp32", "bf16"):
            raise ValueError(f"unknown precision: {self.precision!r} "
                             f"(expected 'fp32' or 'bf16')")
        return jnp.bfloat16 if self.precision == "bf16" else jnp.float32

    @property
    def k_tke(self) -> float:
        """Target turbulent kinetic energy 3/2 u_rms^2."""
        return 1.5 * self.u_rms**2

    @property
    def gas(self) -> GasParams:
        return GasParams(mu=self.rho0 * self.nu, prandtl=self.prandtl,
                         prandtl_turb=self.prandtl_turb)

    @property
    def sound_speed0(self) -> float:
        return self.u_rms / self.mach

    @property
    def p0(self) -> float:
        return self.rho0 * self.sound_speed0**2 / equations.GAMMA

    @property
    def delta_filter(self) -> float:
        """LES filter width: element size over number of nodes per direction."""
        return self.dg.dx / (self.n_poly + 1)

    @property
    def dt(self) -> float:
        """Fixed stable timestep (DG CFL ~ 1/(2N+1)) that divides dt_rl."""
        v_max = self.sound_speed0 + 3.0 * self.u_rms
        dt_stable = self.cfl * self.dg.dx / (v_max * (2 * self.n_poly + 1))
        n_sub = int(np.ceil(self.dt_rl / dt_stable))
        return self.dt_rl / n_sub

    @property
    def n_substeps(self) -> int:
        return int(round(self.dt_rl / self.dt))

    @property
    def n_actions(self) -> int:
        return int(round(self.t_end / self.dt_rl))

    def operators(self) -> dict:
        """Jit-constant operator matrices."""
        dg = self.dg
        _, w = dg.nodes_weights()
        return {
            "D": jnp.asarray(dg.deriv_matrix(), dtype=jnp.float32),
            "inv_w_end": (float(1.0 / w[0]), float(1.0 / w[-1])),
            "w": jnp.asarray(w, dtype=jnp.float32),
        }


def kernel_grad_nut(
    q_prim: jax.Array,
    cs_nodes: jax.Array,
    d_matrix: jax.Array,
    inv_w_end: tuple[float, float],
    delta: float,
    *,
    dg: DGParams | None = None,
    jac=None,
    bc: tuple | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused Pallas hot spots shared by the HIT and channel RHS assemblies:
    one-HBM-pass 3-direction volume derivative feeding the (optionally
    BC-aware) DG gradient, and the fused strain -> nu_t chain.  `dg`/`jac`/
    `bc` forward to dgsem.dg_gradient; the jnp branches of the callers are
    the validated oracle (tests/test_kernel_parity.py)."""
    from ..kernels import ops as kops

    n = q_prim.shape[-2]
    qb = q_prim.reshape((-1, n, n, n, q_prim.shape[-1]))
    vols = kops.dg_derivative3(qb, d_matrix, impl="kernel")
    vol_derivs = tuple(v.reshape(q_prim.shape) for v in vols)
    grad_prim = dgsem.dg_gradient(q_prim, dg, d_matrix, inv_w_end,
                                  vol_derivs=vol_derivs, jac=jac, bc=bc)
    grad_v = grad_prim[..., 0:3, :]
    nu_t = kops.smagorinsky_nut(
        grad_v.reshape((-1, 3, 3)), cs_nodes.reshape((-1,)), delta,
        impl="kernel",
    ).reshape(cs_nodes.shape)
    return grad_prim, nu_t


def broadcast_cs(cs_elem: jax.Array, cfg: HITConfig) -> jax.Array:
    """Per-element coefficients (..., K,K,K) -> nodal field (..., K,K,K,n,n,n)."""
    n = cfg.n_poly + 1
    return jnp.broadcast_to(
        cs_elem[..., None, None, None],
        cs_elem.shape + (n, n, n),
    )


def rhs_gradients(
    q_prim: jax.Array, cs_nodes: jax.Array, cfg: HITConfig, ops: dict
) -> tuple[jax.Array, jax.Array]:
    """Stage 1 of the unfused RHS: BR1 gradient of (v, T) + Smagorinsky
    nu_t.  Exposed as a stage so benchmarks/perf_compare.py can time the
    separate-dispatch (per-stage jit, HBM round-trip) assembly the fused
    mega-kernel replaces."""
    d_matrix, inv_w_end = ops["D"], ops["inv_w_end"]
    grad_prim = dgsem.dg_gradient(q_prim, cfg.dg, d_matrix, inv_w_end)
    grad_v = grad_prim[..., 0:3, :]
    s_mag = equations.strain_magnitude(equations.strain_rate(grad_v))
    nu_t = equations.eddy_viscosity(cs_nodes, cfg.delta_filter, s_mag)
    return grad_prim, nu_t


def rhs_divergence(
    u: jax.Array,
    prim: tuple[jax.Array, ...],
    grad_prim: jax.Array,
    nu_t: jax.Array,
    cfg: HITConfig,
    ops: dict,
) -> jax.Array:
    """Stage 2 of the unfused RHS: -div(F_adv - F_visc) over the three
    directions (split-form volume, LLF + BR1-central surfaces)."""
    dg, gas = cfg.dg, cfg.gas
    d_matrix, inv_w_end = ops["D"], ops["inv_w_end"]
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
        f_star_left = dgsem.left_faces(f_star, d)  # periodic wrap
        div_d = dgsem.surface_lift(vol, f_star - hi, f_star_left - lo, d, inv_w_end)
        div_d = div_d * dg.jac
        rhs = -div_d if rhs is None else rhs - div_d
    return rhs


def rhs_forcing(u: jax.Array, vel: jax.Array, cfg: HITConfig) -> jax.Array:
    """Stage 3 of the unfused RHS: Lundgren linear forcing with the
    proportional TKE controller (whole-box quadrature means)."""
    dg = cfg.dg
    mom = u[..., 1:4]
    mom_mean = dgsem.quadrature_mean(mom, dg)  # (..., 3)
    mom_fluct = mom - mom_mean[..., None, None, None, None, None, None, :]
    ke_density = 0.5 * jnp.sum(mom * vel, axis=-1, keepdims=True)
    k_now = dgsem.quadrature_mean(ke_density, dg)[..., 0]  # (...,)
    a_eff = cfg.forcing_a0 * jnp.clip(cfg.k_tke / jnp.maximum(k_now, 0.1 * cfg.k_tke), 0.0, 3.0)
    a_eff = a_eff[..., None, None, None, None, None, None]
    f_mom = a_eff[..., None] * mom_fluct
    f_e = jnp.sum(f_mom * vel, axis=-1, keepdims=True)
    return jnp.concatenate(
        [jnp.zeros_like(u[..., :1]), f_mom, f_e], axis=-1
    )


def navier_stokes_rhs(
    u: jax.Array, cs_nodes: jax.Array, cfg: HITConfig, ops: dict
) -> jax.Array:
    """-div(F_adv - F_visc) + forcing, the full semi-discrete RHS.

    Advective volume terms use *split-form* flux differencing with the
    Kennedy-Gruber kinetic-energy-preserving two-point flux — FLEXI's
    stabilization for underresolved turbulence (standard-form collocated
    DGSEM aliases and blows up on this test case within a few steps).
    Surface terms use local Lax-Friedrichs; viscous terms are BR1-style
    central.

    With `cfg.kernels_enabled` the whole evaluation is ONE fused Pallas
    launch (kernels/rhs.py: derivative -> fluxes -> eddy viscosity ->
    divergence + forcing with intermediates in VMEM); otherwise the staged
    jnp assembly below runs — it is the kernel's validated oracle
    (tests/test_kernel_parity.py).
    """
    if cfg.kernels_enabled:
        from ..kernels import ops as kops

        return kops.navier_stokes_rhs_fused(
            u, cs_nodes, ops["D"], ops["w"], impl="kernel",
            **_fused_rhs_scalars(cfg, ops))

    rho, vel, p, temp = equations.conservative_to_primitive(u)
    e_spec = u[..., 4] / rho
    prim = (rho, vel, p, e_spec)
    q_prim = jnp.concatenate([vel, temp[..., None]], axis=-1)
    grad_prim, nu_t = rhs_gradients(q_prim, cs_nodes, cfg, ops)
    rhs = rhs_divergence(u, prim, grad_prim, nu_t, cfg, ops)
    return rhs + rhs_forcing(u, vel, cfg)


def _fused_rhs_scalars(cfg: HITConfig, ops: dict) -> dict:
    """The static scalars of the fused RHS kernel (kernels/rhs.py)."""
    return dict(inv_w_end=ops["inv_w_end"], jac=cfg.dg.jac,
                delta=cfg.delta_filter, mu=cfg.gas.mu, prandtl=cfg.prandtl,
                prandtl_turb=cfg.prandtl_turb, forcing_a0=cfg.forcing_a0,
                k_tke=cfg.k_tke)


@jax.named_scope("solver.rk_substep")   # repro.obs: its ops' scope
def rk_substep(u: jax.Array, rhs: Callable[[jax.Array], jax.Array],
               dt: float) -> jax.Array:
    """One low-storage RK5(4) step of size dt.  `rhs(u)` is the
    semi-discrete RHS in u's layout: the stage arithmetic is elementwise,
    so one function serves the natural and the planar carry."""
    dt = jnp.asarray(dt, dtype=u.dtype)
    du = jnp.zeros_like(u)
    for stage in range(5):
        # the cast keeps the carry in the rollout compute dtype: the jnp RHS
        # promotes a bf16 state to f32 (float32 operator matrices), while
        # the fused kernel already returns u.dtype — both are no-ops in the
        # default fp32 path.  RK constants go through float() so the weak
        # python scalar cannot re-promote a bf16 carry.
        r = rhs(u).astype(u.dtype)
        du = float(_RK_A[stage]) * du + dt * r
        u = u + float(_RK_B[stage]) * du
    return u


@functools.partial(jax.jit, static_argnames=("cfg",))
def advance_rl_interval(u: jax.Array, cs_elem: jax.Array, cfg: HITConfig) -> jax.Array:
    """Advance the LES by Delta t_RL under fixed per-element C_s (one MDP
    transition).  This is the unit of work the paper distributes over MPI
    ranks; here it is one XLA program.

    With `cfg.kernels_enabled` the RK carry stays in the fused kernel's
    planar layout (kernels/rhs.py) for the whole interval: the state and
    the nodal C_s are converted once before the substep scan
    (`to_planar_batch`, batch padded to whole kernel blocks) and back once
    after it (`from_planar_batch`), both under the `rhs.layout` scope, and
    every RK stage calls the planar kernel entry directly.  Otherwise the
    staged jnp RHS steps the natural layout — the oracle.

    With `cfg.precision == "bf16"` the state is advanced in bfloat16 for
    the whole interval (the mixed-precision rollout) and cast back to
    float32 at the boundary, so observations/reward/PPO stay float32."""
    ops = cfg.operators()
    cs_nodes = broadcast_cs(cs_elem, cfg)
    dtype = cfg.compute_dtype
    u = u.astype(dtype)
    cs_nodes = cs_nodes.astype(dtype)
    if dtype != jnp.float32:
        # cast the operator matrices to the compute dtype too, or every
        # D @ u / quadrature contraction re-promotes the carry to f32 and
        # demotes it back each RK stage (a state-sized round trip per
        # substep — the churn JAX002 guards against)
        ops = dict(ops, D=ops["D"].astype(dtype), w=ops["w"].astype(dtype))

    if cfg.kernels_enabled:
        from ..kernels import ops as kops
        from ..kernels.rhs import from_planar_batch, to_planar_batch

        x, cs_pl, block_e = to_planar_batch(u, cs_nodes)

        def rhs(x):
            return kops.navier_stokes_rhs_planar(
                x, cs_pl, ops["D"], ops["w"], k=cfg.n_elem, block_e=block_e,
                impl="kernel", **_fused_rhs_scalars(cfg, ops))
    else:
        x = u

        def rhs(x):
            return navier_stokes_rhs(x, cs_nodes, cfg, ops)

    def body(x, _):
        return rk_substep(x, rhs, cfg.dt), None

    x, _ = jax.lax.scan(body, x, None, length=cfg.n_substeps)
    if cfg.kernels_enabled:
        x = from_planar_batch(x, u.shape)
    return x.astype(jnp.float32)
