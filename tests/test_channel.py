"""Wall-BC DGSEM tests: the channel substrate's boundary abstraction.

Pins the two contracts the BC refactor promises: (i) with walls disabled
the mixed-BC assembly is BIT-IDENTICAL to the periodic HIT path, and
(ii) with walls enabled the weak wall fluxes conserve mass exactly while
exchanging momentum/energy only through the modeled wall stress."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.cfd import channel, dgsem, equations, initial, solver
from repro.cfd.channel import ChannelConfig
from repro.cfd.dgsem import DGParams
from repro.cfd.solver import HITConfig

CFG = ChannelConfig(n_elem=(2, 3, 2), t_end=0.3)


def _weights_dg(cfg: ChannelConfig) -> DGParams:
    """DGParams stand-in for quadrature weights (element count is read off
    the array by dgsem.quadrature_mean, so any K works)."""
    return DGParams(cfg.n_poly, 1)


def _neutral_scales(cfg: ChannelConfig, value: float = 1.0):
    kx, _, kz = cfg.n_elem
    s = jnp.full((kx, kz), value, jnp.float32)
    return s, s


# --- BC abstraction ---------------------------------------------------------
def test_left_faces_periodic_is_roll():
    x = jnp.arange(2 * 3 * 2 * 4 * 4 * 5, dtype=jnp.float32).reshape(
        (2, 3, 2, 4, 4, 5))  # y-face array: node axis of d=1 removed
    np.testing.assert_array_equal(
        np.asarray(dgsem.left_faces(x, 1)),
        np.asarray(jnp.roll(x, 1, axis=1)))


def test_left_faces_wall_overrides_element_zero():
    x = jnp.ones((2, 3, 2, 4, 4, 5), jnp.float32)
    bc = jnp.full((2, 2, 4, 4, 5), 7.0, jnp.float32)
    out = dgsem.left_faces(x, 1, lo_value=bc)
    np.testing.assert_array_equal(np.asarray(out[:, 0]), np.asarray(bc))
    np.testing.assert_array_equal(np.asarray(out[:, 1:]),
                                  np.ones((2, 2, 2, 4, 4, 5), np.float32))


def test_set_face_hi():
    x = jnp.zeros((2, 3, 2, 4, 4, 5), jnp.float32)
    bc = jnp.full((2, 2, 4, 4, 5), 3.0, jnp.float32)
    out = dgsem.set_face(x, 1, -1, bc)
    np.testing.assert_array_equal(np.asarray(out[:, -1]), np.asarray(bc))
    assert float(jnp.sum(jnp.abs(out[:, :-1]))) == 0.0


# --- reduction to the periodic path ----------------------------------------
def test_wall_off_reduces_to_periodic_hit_rhs():
    """cfg.wall=False on a cubic box must reproduce the periodic HIT RHS
    bit-for-bit (same helpers, same assembly order)."""
    length = 2.0 * np.pi
    hit = HITConfig(n_poly=3, n_elem=2, forcing_a0=0.0, nu=5e-3)
    ch = ChannelConfig(n_poly=3, n_elem=(2, 2, 2),
                       lengths=(length, length, length), nu=5e-3,
                       mach=hit.mach, u_bulk=hit.u_rms, wall=False,
                       u_tau=0.0, cs_sgs=0.17)
    u = initial.sample_initial_state(jax.random.PRNGKey(0), hit)
    cs_nodes = jnp.full(u.shape[:-1], 0.17, u.dtype)
    r_hit = solver.navier_stokes_rhs(u, cs_nodes, hit, hit.operators())
    scales = _neutral_scales(ch)
    r_ch = channel.channel_rhs(u, *scales, ch, ch.operators())
    np.testing.assert_array_equal(np.asarray(r_hit), np.asarray(r_ch))


# --- conservation with walls on --------------------------------------------
def test_wall_bc_conserves_mass():
    """The wall mass flux is exactly zero and the interior split form is
    conservative: total mass must survive many RL intervals to round-off."""
    u0 = channel.sample_initial_state(jax.random.PRNGKey(1), CFG)
    u = u0
    for _ in range(3):
        u = channel.advance_rl_interval(u, *_neutral_scales(CFG), CFG)
    assert bool(jnp.all(jnp.isfinite(u)))
    m0 = dgsem.quadrature_mean(u0, _weights_dg(CFG))
    m1 = dgsem.quadrature_mean(u, _weights_dg(CFG))
    np.testing.assert_allclose(float(m1[0]), float(m0[0]), rtol=1e-6)


def test_wall_stress_decelerates_unforced_flow():
    """Without forcing the only x-momentum sink is the modeled wall stress:
    bulk momentum must decrease, and faster with a larger stress scaling.
    The state is drawn at the config's own u_tau (u_tau also scales the
    reference profile: drawn at 0 it has no bulk flow); only the advance
    runs with the forcing off."""
    u0 = channel.sample_initial_state(jax.random.PRNGKey(2), CFG)
    cfg = dataclasses.replace(CFG, u_tau=0.0)  # f_x = 0, walls still on
    mom0 = float(dgsem.quadrature_mean(u0, _weights_dg(cfg))[1])
    assert mom0 > 0.0
    moms = {}
    for a in (0.5, 2.0):
        u = channel.advance_rl_interval(u0, *_neutral_scales(cfg, a), cfg)
        moms[a] = float(dgsem.quadrature_mean(u, _weights_dg(cfg))[1])
    assert moms[0.5] < mom0
    assert moms[2.0] < moms[0.5]


def test_wall_model_laminar_limit():
    """In the viscous sublayer (tiny y+) the inverted wall law must reduce
    to the laminar stress mu * u_par / y_m."""
    cfg = CFG
    u_par = jnp.asarray(0.01, jnp.float32)
    y_m = 1e-3
    tau = channel.wall_stress_magnitude(u_par, jnp.asarray(cfg.rho0), y_m, cfg)
    np.testing.assert_allclose(float(tau),
                               cfg.rho0 * cfg.nu * float(u_par) / y_m,
                               rtol=1e-2)


def test_reference_profile_symmetric_and_positive():
    ref = channel.reference_profile(CFG)
    assert ref.shape == (CFG.n_elem[1], CFG.n)
    flat = ref.reshape(-1)
    np.testing.assert_allclose(flat, flat[::-1], atol=1e-6)
    assert (ref >= 0.0).all()
    assert float(ref.max()) > CFG.u_tau  # outer flow well above u_tau


def test_profile_error_batch_shapes():
    """Profile + reward reduce correctly over a leading env batch."""
    ops = CFG.operators()
    bank = channel.make_state_bank(jax.random.PRNGKey(3), CFG, 2)
    prof = channel.mean_velocity_profile(bank, CFG, ops)
    assert prof.shape == (2, CFG.n_elem[1], CFG.n)
    ref = jnp.asarray(channel.reference_profile(CFG))
    ell = channel.profile_error(prof, ref, ops)
    assert ell.shape == (2,)
    assert bool(jnp.all(jnp.isfinite(ell)))
