"""Hypothesis property tests on system invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

# Skip (not crash) the whole module when hypothesis isn't installed, so the
# rest of the suite still collects and runs.
pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.cfd import spectra
from repro.core import ppo
from repro.kernels import ref
from repro.parallel import sharding as shd
from repro.launch import mesh as mesh_lib

_settings = settings(max_examples=25, deadline=None)


@_settings
@given(st.floats(0.0, 50.0), st.floats(0.05, 2.0))
def test_reward_bounded_and_monotone(ell, alpha):
    r = float(spectra.reward_from_error(jnp.asarray(ell), alpha))
    assert -1.0 <= r <= 1.0
    r2 = float(spectra.reward_from_error(jnp.asarray(ell + 0.1), alpha))
    assert r2 <= r + 1e-9  # lower spectral error is never worse


@_settings
@given(st.floats(0.5, 8.0), st.floats(8.0, 64.0), st.floats(0.2, 3.0))
def test_vkp_spectrum_positive_and_normalized(k_peak, k_eta, u_rms):
    k = np.arange(32)
    e = spectra.vkp_spectrum(k, u_rms, k_peak, k_eta)
    assert np.all(e >= 0.0) and e[0] == 0.0
    np.testing.assert_allclose(e.sum(), 1.5 * u_rms**2, rtol=1e-10)


@_settings
@given(st.integers(1, 6), st.integers(1, 4), st.floats(0.8, 1.0),
       st.floats(0.8, 1.0))
def test_gae_of_zero_rewards_zero_values_is_zero(t, b, gamma, lam):
    traj_r = jnp.zeros((t, b))
    traj_v = jnp.zeros((t, b))
    traj = ppo.Trajectory(
        obs=jnp.zeros((t, b, 1, 2, 2, 2, 3)), actions=jnp.zeros((t, b, 1)),
        log_probs=jnp.zeros((t, b)), rewards=traj_r,
        dones=jnp.zeros((t, b), bool).at[-1].set(True),
        values=traj_v, last_value=jnp.zeros((b,)))
    adv, ret = ppo.gae(traj, gamma, lam)
    np.testing.assert_allclose(np.asarray(adv), 0.0, atol=1e-7)
    np.testing.assert_allclose(np.asarray(ret), 0.0, atol=1e-7)


@_settings
@given(st.integers(1, 32), st.integers(1, 17), st.integers(1, 8))
def test_logical_to_spec_never_breaks_divisibility(d0, d1, d2):
    mesh = mesh_lib.auto_mesh((1,), ("model",))
    rules = shd.AxisRules(mesh, {"a": "model", "b": "model", "c": None})
    spec = shd.logical_to_spec((d0, d1, d2), ("a", "b", "c"), rules)
    assert len(spec) == 3
    for dim, s in zip((d0, d1, d2), spec):
        if s is not None:
            assert dim % mesh.shape[s if isinstance(s, str) else s[0]] == 0


def test_logical_to_spec_drops_consumed_axes():
    mesh = mesh_lib.auto_mesh((1,), ("model",))
    rules = shd.AxisRules(mesh, {"a": "model", "b": "model"})
    spec = shd.logical_to_spec((4, 4), ("a", "b"), rules)
    # the second dim must not reuse the axis the first consumed
    named = [s for s in spec if s is not None]
    assert len(named) <= 1


@_settings
@given(st.integers(2, 24), st.integers(1, 3),
       st.floats(0.55, 0.999), st.booleans())
def test_linear_scan_decay_contracts_state(t, b, w_val, dbr):
    """With k=0 inputs the state must decay monotonically (|S| shrinking) —
    the stability property the chunked kernel relies on."""
    dk, dv = 4, 4
    q = jnp.zeros((b, t, dk))
    k = jnp.zeros((b, t, dk))
    v = jnp.zeros((b, t, dv))
    w = jnp.full((b, t, dk), w_val)
    s0 = jnp.ones((b, dk, dv))
    o, s = ref.linear_scan_chunked(q, k, v, w, None, s0,
                                   decay_before_read=dbr, chunk=8)
    np.testing.assert_allclose(np.asarray(s), w_val**t, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(o), 0.0, atol=1e-7)


@_settings
@given(st.floats(1e-3, 3.0), st.floats(1e-3, 2.9), st.floats(0.01, 0.3),
       st.floats(1e-4, 1e-2))
def test_wall_model_tau_monotone_in_matching_velocity(u1, du, y_m, nu):
    """tau_w from the Reichardt inversion must increase with the
    matching-point velocity — faster outer flow never lowers the modeled
    wall friction (the sign the RL action relies on)."""
    rho = jnp.ones(())
    kw = dict(y_m=y_m, nu=nu, iters=8)
    t1 = float(ref.wall_model_tau(jnp.asarray(u1), rho, **kw))
    t2 = float(ref.wall_model_tau(jnp.asarray(u1 + du), rho, **kw))
    assert t2 >= t1 * (1.0 - 1e-6)
    assert t1 > 0.0


@_settings
@given(st.floats(1e-3, 3.0), st.floats(0.01, 0.3), st.floats(1e-4, 1e-2))
def test_wall_model_fixed_point_converges_within_budget(u_par, y_m, nu):
    """The damped fixed point must be converged at the production iteration
    budget: doubling `iters` moves tau_w by < 1%, and the converged u_tau
    satisfies the wall law u_par/u_tau = u+(y_m u_tau / nu)."""
    rho = jnp.ones(())
    t8 = float(ref.wall_model_tau(jnp.asarray(u_par), rho, y_m=y_m, nu=nu,
                                  iters=8))
    t16 = float(ref.wall_model_tau(jnp.asarray(u_par), rho, y_m=y_m, nu=nu,
                                   iters=16))
    assert abs(t16 - t8) <= 1e-2 * abs(t16) + 1e-10
    u_tau = np.sqrt(t16)  # rho = 1
    u_plus = float(ref.reichardt_uplus(jnp.asarray(y_m * u_tau / nu)))
    np.testing.assert_allclose(u_par / u_tau, u_plus, rtol=2e-2)


@_settings
@given(st.floats(0.0, 2.0))
def test_wall_flux_affine_in_action_scale(a):
    """The wall flux is affine in the action: the advective (pressure) part
    is a-independent and the modeled viscous stress scales linearly, so
    f(a) = f(0) + a * (f(1) - f(0)) — in particular a=1 recovers the
    unscaled equilibrium wall model exactly."""
    from repro.cfd import channel
    from repro.cfd.channel import ChannelConfig

    cfg = ChannelConfig(n_elem=(2, 3, 2))
    ops_ch = cfg.operators()
    u = channel.sample_initial_state(jax.random.PRNGKey(11), cfg)
    kx, _, kz = cfg.n_elem
    n = cfg.n

    def fluxes(scale):
        s = jnp.full((kx, kz, n, n), scale, jnp.float32)
        lo, hi = channel.wall_fluxes(u, s, s, cfg, ops_ch)
        return np.asarray(lo), np.asarray(hi)

    f0, f1, fa = fluxes(0.0), fluxes(1.0), fluxes(float(a))
    for lo_hi in range(2):
        want = f0[lo_hi] + a * (f1[lo_hi] - f0[lo_hi])
        np.testing.assert_allclose(fa[lo_hi], want, rtol=1e-5, atol=1e-7)


@_settings
@given(st.integers(0, 1000), st.integers(1, 64), st.integers(1, 64))
def test_ring_buffer_slot_positions_valid(pos, length, _unused):
    """Every warm ring-buffer slot holds a position in (pos-L, pos]."""
    slots = np.arange(length)
    abs_pos = pos - np.mod(pos - slots, length)
    assert np.all(abs_pos <= pos)
    assert np.all(abs_pos > pos - length)


@_settings
@given(st.data())
def test_mha_chunked_equals_naive(data):
    b = data.draw(st.integers(1, 2))
    h = data.draw(st.sampled_from([1, 2, 4]))
    hkv = data.draw(st.sampled_from([x for x in (1, 2, 4) if h % x == 0]))
    sq = data.draw(st.integers(1, 24))
    skv = data.draw(st.integers(sq, 32))
    d = data.draw(st.sampled_from([4, 8]))
    block = data.draw(st.sampled_from([4, 8, 16]))
    key = jax.random.PRNGKey(data.draw(st.integers(0, 2**30)))
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, h, sq, d))
    k = jax.random.normal(ks[1], (b, hkv, skv, d))
    v = jax.random.normal(ks[2], (b, hkv, skv, d))
    a = ref.mha_chunked(q, k, v, causal=True, block_k=block)
    want = ref.mha(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


@_settings
@given(st.integers(1, 100), st.integers(1, 8), st.floats(1.0, 2.0))
def test_moe_capacity_is_sufficient_and_aligned(group, topk, cf):
    from repro.models import moe
    from repro.models.config import ArchConfig
    cfg = ArchConfig(name="t", family="moe", n_layers=1, d_model=8,
                     n_heads=1, kv_heads=1, d_ff=8, vocab=8, n_experts=8,
                     top_k=topk, moe_capacity_factor=cf)
    cap = moe._capacity(group, cfg)
    assert cap % 8 == 0 and cap >= 8
    assert cap * cfg.n_experts >= group * topk * min(cf, 1.0) * 0.99


def test_config_validation_all_archs():
    """Every assigned config satisfies its own structural invariants."""
    from repro import configs
    from repro.models import lm
    for name in configs.ARCH_NAMES:
        cfg = configs.get(name)
        assert cfg.n_heads % cfg.kv_heads == 0, name
        if not cfg.is_encdec:
            lm.n_groups(cfg)  # raises if the scan grouping doesn't divide
        if cfg.ffn == "moe":
            assert 0 < cfg.top_k <= cfg.n_experts
        if cfg.mixer == "attn+mamba":
            assert cfg.ssm_state > 0
        assert cfg.approx_params() > 0
