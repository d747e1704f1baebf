"""Kernel <-> reference parity gate (`pytest -m kernel_parity -q`).

Every Pallas solver-kernel entry point — the fused `navier_stokes_rhs`
mega-kernel, `dg_derivative3`, `smagorinsky_nut` and `wall_model_tau` — is
swept over a dtype x shape x block-size grid in
interpret mode against its pure-jnp oracle in `kernels/ref.py`, with pinned
per-kernel tolerances; plus full-path regressions proving a complete RHS /
env step with `use_kernels=True` matches the reference assembly.  This gate
is what lets kernels default ON for TPU runs (kernels.default_impl()):
any future kernel edit that drifts from the oracle fails here first.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cfd import channel, solver
from repro.cfd.channel import ChannelConfig
from repro.cfd.solver import HITConfig
from repro.envs import registry
from repro.kernels import ops, ref
from repro.kernels.dg_derivative import dg_derivative3
from repro.kernels.smagorinsky import smagorinsky_nut
from repro.kernels.wall_model import wall_model_tau

pytestmark = pytest.mark.kernel_parity

# Pinned per-kernel tolerances.  float32 paths do the same math in the same
# order (kernels accumulate in f32); bfloat16 tolerances cover the 8-bit
# mantissa of the in/out casts.
TOL = {
    "navier_stokes_rhs_fused": {jnp.float32: dict(rtol=2e-4, atol=2e-4),
                                jnp.bfloat16: dict(rtol=4e-2, atol=4e-2)},
    "dg_derivative3": {jnp.float32: dict(rtol=2e-4, atol=1e-5),
                       jnp.bfloat16: dict(rtol=4e-2, atol=4e-2)},
    "smagorinsky_nut": {jnp.float32: dict(rtol=2e-5, atol=1e-7),
                        jnp.bfloat16: dict(rtol=4e-2, atol=4e-3)},
    "wall_model_tau": {jnp.float32: dict(rtol=1e-5, atol=1e-8),
                       jnp.bfloat16: dict(rtol=4e-2, atol=4e-4)},
}


def _assert_close(kernel_name, dtype, got, want):
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               **TOL[kernel_name][dtype])


# --- fused Navier-Stokes RHS mega-kernel ------------------------------------
def _synthetic_state(key, shape_prefix, cfg):
    """Physically plausible conservative state: rho ~ 1, subsonic velocity,
    pressure well clear of vacuum — keeps sqrt/temperature paths benign."""
    n = cfg.n_poly + 1
    k = cfg.n_elem
    mesh = shape_prefix + (k, k, k, n, n, n)
    kr, kv, kp = jax.random.split(key, 3)
    rho = 1.0 + 0.1 * jax.random.uniform(kr, mesh + (1,))
    vel = 0.3 * jax.random.normal(kv, mesh + (3,))
    p = 7.0 + 0.5 * jax.random.uniform(kp, mesh + (1,))
    e = p / 0.4 + 0.5 * rho * jnp.sum(vel**2, axis=-1, keepdims=True)
    return jnp.concatenate([rho, rho * vel, e], axis=-1)


def _fused_rhs_kwargs(cfg):
    ops_d = cfg.operators()
    return ops_d, dict(inv_w_end=ops_d["inv_w_end"], jac=cfg.dg.jac,
                       delta=cfg.delta_filter, mu=cfg.gas.mu,
                       prandtl=cfg.prandtl, prandtl_turb=cfg.prandtl_turb,
                       forcing_a0=cfg.forcing_a0, k_tke=cfg.k_tke)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("prefix,n_poly,n_elem,block_e", [
    ((), 3, 2, 1),      # single mesh, production-reduced polynomial order
    ((3,), 3, 2, 2),    # batch with padding (3 % 2 != 0)
    ((4,), 2, 3, 4),    # K=3 periodic exchange, whole batch in one block
])
def test_fused_rhs_parity(prefix, n_poly, n_elem, block_e, dtype):
    from repro.kernels.rhs import fused_navier_stokes_rhs

    cfg = HITConfig(n_poly=n_poly, n_elem=n_elem, use_kernels=False)
    ops_d, kw = _fused_rhs_kwargs(cfg)
    u = _synthetic_state(jax.random.PRNGKey(3), prefix, cfg).astype(dtype)
    cs = jnp.full(u.shape[:-1], 0.17, dtype)
    got = fused_navier_stokes_rhs(u, cs, ops_d["D"], ops_d["w"],
                                  block_e=block_e, interpret=True, **kw)
    want = ref.navier_stokes_rhs_fused(u, cs, ops_d["D"], ops_d["w"], **kw)
    assert got.shape == u.shape and got.dtype == u.dtype
    _assert_close("navier_stokes_rhs_fused", dtype, got, want)


def test_fused_rhs_oracle_matches_solver_assembly():
    """The self-contained `ref.navier_stokes_rhs_fused` oracle reproduces the
    stage-by-stage solver assembly to float32 rounding (same ops; its
    planar layout sums the node-axis contractions offset by offset) — the
    anchor that ties the mega-kernel's parity gate back to the physics."""
    from repro.cfd import initial

    cfg = HITConfig(n_poly=3, n_elem=2, use_kernels=False)
    ops_d, kw = _fused_rhs_kwargs(cfg)
    u = initial.sample_initial_state(jax.random.PRNGKey(4), cfg)
    cs = jnp.full(u.shape[:-1], 0.17, u.dtype)
    want = solver.navier_stokes_rhs(u, cs, cfg, ops_d)
    got = ref.navier_stokes_rhs_fused(u, cs, ops_d["D"], ops_d["w"], **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# --- planar RK carry --------------------------------------------------------
def test_planar_batch_round_trip():
    """The interval's conversions: the batch padded to whole kernel blocks
    with copies of mesh 0 (3 meshes of 4^3 elements, 2 per 128-lane block),
    and back to the natural layout exactly."""
    from repro.kernels.rhs import from_planar_batch, to_planar_batch

    cfg = HITConfig(n_poly=2, n_elem=4, use_kernels=False)
    u = _synthetic_state(jax.random.PRNGKey(10), (3,), cfg)
    cs = jax.random.uniform(jax.random.PRNGKey(11), u.shape[:-1])
    u_pl, cs_pl, block_e = to_planar_batch(u, cs)
    assert block_e == 2
    assert u_pl.shape == (5, 27, 4 * 64) and cs_pl.shape == (27, 4 * 64)
    np.testing.assert_array_equal(np.asarray(u_pl[..., 3 * 64:]),
                                  np.asarray(u_pl[..., :64]))
    np.testing.assert_array_equal(np.asarray(cs_pl[:, 3 * 64:]),
                                  np.asarray(cs_pl[:, :64]))
    np.testing.assert_array_equal(
        np.asarray(from_planar_batch(u_pl, u.shape)), np.asarray(u))
    np.testing.assert_array_equal(
        np.asarray(from_planar_batch(cs_pl[None], cs.shape + (1,))[..., 0]),
        np.asarray(cs))


@functools.partial(jax.jit, static_argnames="cfg")
def _per_call_interval(u, cs_elem, cfg):
    """The RL interval on the natural-layout carry, the fused RHS wrapper
    converting into and out of the planar layout around every call."""
    dtype = cfg.compute_dtype
    ops_d = cfg.operators()
    ops_d = dict(ops_d, D=ops_d["D"].astype(dtype), w=ops_d["w"].astype(dtype))
    cs = solver.broadcast_cs(cs_elem, cfg).astype(dtype)

    def body(x, _):
        return solver.rk_substep(
            x, lambda y: solver.navier_stokes_rhs(y, cs, cfg, ops_d),
            cfg.dt), None

    x, _ = jax.lax.scan(body, u.astype(dtype), None, length=cfg.n_substeps)
    return x.astype(jnp.float32)


@pytest.mark.parametrize("precision", ["fp32", "bf16"])
def test_planar_rk_carry_matches_per_call_layout(precision):
    """`advance_rl_interval` with kernels on keeps the RK carry planar for
    the whole interval; it gives the same bits as the natural-layout carry
    with a layout round trip around each RHS call (3 meshes, so the lanes
    need padding)."""
    cfg = HITConfig(n_poly=1, n_elem=4, dt_rl=0.04, use_kernels=True,
                    precision=precision)
    assert cfg.n_substeps > 1
    u = _synthetic_state(jax.random.PRNGKey(12), (3,), cfg)
    cs_elem = jax.random.uniform(jax.random.PRNGKey(13), (3, 4, 4, 4),
                                 maxval=0.3)
    got = solver.advance_rl_interval(u, cs_elem, cfg)
    want = _per_call_interval(u, cs_elem, cfg)
    assert got.shape == u.shape and got.dtype == jnp.float32
    assert np.all(np.isfinite(np.asarray(got)))
    assert not np.array_equal(np.asarray(got), np.asarray(u))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_planar_rhs_ops_dispatch_matches_ref():
    """The planar dispatch: the kernel ("kernel" forced, interpret off-TPU)
    against `ref.navier_stokes_rhs_planar` on the whole array."""
    from repro.kernels.rhs import to_planar_batch

    cfg = HITConfig(n_poly=1, n_elem=4, use_kernels=False)
    ops_d, kw = _fused_rhs_kwargs(cfg)
    u = _synthetic_state(jax.random.PRNGKey(14), (3,), cfg)
    u_pl, cs_pl, block_e = to_planar_batch(u, jnp.full(u.shape[:-1], 0.17))
    got, want = (ops.navier_stokes_rhs_planar(
        u_pl, cs_pl, ops_d["D"], ops_d["w"], k=4, block_e=block_e,
        impl=impl, **kw) for impl in ("kernel", "ref"))
    assert got.shape == u_pl.shape and got.dtype == u_pl.dtype
    _assert_close("navier_stokes_rhs_fused", jnp.float32, got, want)


# --- dg_derivative3 ---------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,c,b,block_b", [
    (4, 5, 16, 8),    # even split
    (6, 3, 10, 4),    # padding (10 % 4 != 0)
    (8, 1, 7, 16),    # block larger than batch
    (4, 4, 27, 9),    # K^3 element batch, odd block
])
def test_dg_derivative3_parity(n, c, b, block_b, dtype):
    u = jax.random.normal(jax.random.PRNGKey(5), (b, n, n, n, c), dtype)
    d = jax.random.normal(jax.random.PRNGKey(6), (n, n), jnp.float32)
    outs = dg_derivative3(u, d, block_b=block_b, interpret=True)
    wants = ref.dg_derivative3(u, d)
    assert all(o.dtype == u.dtype for o in outs)
    for got, want in zip(outs, wants):
        _assert_close("dg_derivative3", dtype, got, want)


# --- smagorinsky_nut --------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("p,block_p", [
    (17, 8),       # padding
    (2048, 512),   # even multi-block
    (64, 128),     # block larger than batch
])
def test_smagorinsky_parity(p, block_p, dtype):
    ks = jax.random.split(jax.random.PRNGKey(7), 2)
    grad_v = jax.random.normal(ks[0], (p, 3, 3), dtype)
    cs = jax.random.uniform(ks[1], (p,), minval=0.0, maxval=0.5).astype(dtype)
    got = smagorinsky_nut(grad_v, cs, 0.1, block_p=block_p, interpret=True)
    want = ref.smagorinsky_nut(grad_v, cs, 0.1)
    assert got.dtype == grad_v.dtype
    _assert_close("smagorinsky_nut", dtype, got, want)


# --- wall_model_tau ---------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape,block_p", [
    ((64,), 32),         # flat even split
    ((2, 24, 16), 128),  # (B, n_wall_elems, face_dofs) batch, padding
    ((7,), 64),          # tiny odd batch, block larger than batch
])
def test_wall_model_parity(shape, block_p, dtype):
    ks = jax.random.split(jax.random.PRNGKey(8), 2)
    # u_par spans the viscous sublayer through the log layer
    u_par = jax.random.uniform(ks[0], shape, minval=1e-3,
                               maxval=3.0).astype(dtype)
    rho_w = jax.random.uniform(ks[1], shape, minval=0.8,
                               maxval=1.2).astype(dtype)
    kw = dict(y_m=0.05, nu=5e-3, kappa=0.41, iters=8)
    got = wall_model_tau(u_par, rho_w, block_p=block_p, interpret=True, **kw)
    want = ref.wall_model_tau(u_par, rho_w, **kw)
    assert got.shape == shape and got.dtype == u_par.dtype
    _assert_close("wall_model_tau", dtype, got, want)


def test_wall_model_ops_dispatch_matches_ref():
    """The ops-layer dispatch ("kernel" forced, off-TPU interpret) and "ref"
    agree — the exact switch ChannelConfig.kernels_enabled flips."""
    u_par = jnp.linspace(1e-3, 2.0, 37)
    rho = jnp.ones_like(u_par)
    kw = dict(y_m=0.1, nu=1e-3, iters=8)
    got = ops.wall_model_tau(u_par, rho, impl="kernel", **kw)
    want = ops.wall_model_tau(u_par, rho, impl="ref", **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **TOL["wall_model_tau"][jnp.float32])


# --- full-path regressions --------------------------------------------------
def test_hit_rhs_kernel_path_matches_reference():
    """Complete HIT RHS with use_kernels forced on (interpret mode off-TPU)
    vs the pure-jnp assembly."""
    from repro.cfd import initial

    cfg_ref = HITConfig(n_poly=3, n_elem=2, use_kernels=False)
    cfg_ker = dataclasses.replace(cfg_ref, use_kernels=True)
    u = initial.sample_initial_state(jax.random.PRNGKey(0), cfg_ref)
    cs = jnp.full(u.shape[:-1], 0.17, u.dtype)
    r_ref = solver.navier_stokes_rhs(u, cs, cfg_ref, cfg_ref.operators())
    r_ker = solver.navier_stokes_rhs(u, cs, cfg_ker, cfg_ker.operators())
    np.testing.assert_allclose(np.asarray(r_ker), np.asarray(r_ref),
                               rtol=2e-4, atol=2e-4)


def test_channel_rhs_kernel_path_matches_reference():
    """Complete wall-BC channel RHS through all three kernels (volume
    derivative, eddy viscosity, wall-model inversion) vs the reference."""
    cfg_ref = ChannelConfig(n_elem=(2, 3, 2), use_kernels=False)
    cfg_ker = dataclasses.replace(cfg_ref, use_kernels=True)
    u = channel.sample_initial_state(jax.random.PRNGKey(1), cfg_ref)
    kx, _, kz = cfg_ref.n_elem
    n = cfg_ref.n
    scale = jnp.broadcast_to(jnp.float32(1.3), (kx, kz, n, n))
    r_ref = channel.channel_rhs(u, scale, scale, cfg_ref, cfg_ref.operators())
    r_ker = channel.channel_rhs(u, scale, scale, cfg_ker, cfg_ker.operators())
    np.testing.assert_allclose(np.asarray(r_ker), np.asarray(r_ref),
                               rtol=2e-4, atol=2e-4)


def test_hit_env_step_kernel_parity():
    """Full `hit_les_reduced` env transition with use_kernels=True (fused
    RHS mega-kernel, interpret off-TPU) matches the reference path."""
    env_ref = registry.make("hit_les_reduced", use_kernels=False)
    env_ker = registry.make("hit_les_reduced", use_kernels=True)
    bank = env_ref.initial_state_bank(jax.random.PRNGKey(9), 1)
    state, obs0 = env_ref.reset_from_bank(bank, jnp.int32(0))
    action = jnp.full((env_ref.action_spec.n_elements,), 0.17, jnp.float32)
    res_ref = env_ref.step(state, action)
    res_ker = env_ker.step(state, action)
    np.testing.assert_allclose(np.asarray(res_ker.state.u),
                               np.asarray(res_ref.state.u),
                               rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(np.asarray(res_ker.obs),
                               np.asarray(res_ref.obs),
                               rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(float(res_ker.reward), float(res_ref.reward),
                               atol=1e-4)
    assert bool(res_ker.done) == bool(res_ref.done)


def test_channel_env_step_kernel_parity():
    """Full `channel_wm` env transition (one RL interval: n_substeps x 5 RK
    stages, obs + reward) with use_kernels=True matches the reference path
    within float32 tolerance — the acceptance gate for default-on kernels."""
    env_ref = registry.make("channel_wm_reduced", use_kernels=False)
    env_ker = registry.make("channel_wm_reduced", use_kernels=True)
    bank = env_ref.initial_state_bank(jax.random.PRNGKey(2), 1)
    state, obs0 = env_ref.reset_from_bank(bank, jnp.int32(0))
    action = jnp.full((env_ref.action_spec.n_elements,), 1.2, jnp.float32)
    res_ref = env_ref.step(state, action)
    res_ker = env_ker.step(state, action)
    np.testing.assert_allclose(np.asarray(res_ker.state.u),
                               np.asarray(res_ref.state.u),
                               rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(np.asarray(res_ker.obs),
                               np.asarray(res_ref.obs),
                               rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(float(res_ker.reward), float(res_ref.reward),
                               atol=1e-4)
    assert bool(res_ker.done) == bool(res_ref.done)


# --- REPRO_KERNELS env override ---------------------------------------------
def test_repro_kernels_env_override(monkeypatch):
    """The env var retargets only the *auto* resolution: default_impl() and
    resolve_use_kernels(None) follow it, explicit choices still win."""
    from repro.kernels import policy

    monkeypatch.setenv("REPRO_KERNELS", "kernel")
    assert policy.default_impl() == "kernel"
    assert policy.resolve_use_kernels(None) is True
    assert policy.resolve_use_kernels(False) is False

    monkeypatch.setenv("REPRO_KERNELS", "ref")
    assert policy.default_impl() == "ref"
    assert policy.resolve_use_kernels(None) is False
    assert policy.resolve_use_kernels(True) is True

    backend_default = "kernel" if jax.default_backend() == "tpu" else "ref"
    for val in ("auto", ""):
        monkeypatch.setenv("REPRO_KERNELS", val)
        assert policy.default_impl() == backend_default

    monkeypatch.setenv("REPRO_KERNELS", "bogus")
    with pytest.raises(ValueError, match="REPRO_KERNELS"):
        policy.default_impl()
