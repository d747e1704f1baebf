"""The wall-modelled channel on the fused planar path.

`cfd/channel.advance_rl_interval` with kernels on steps a planar RK carry
through the `fused_wall_rhs` kernel (kernels/rhs.py), whose body is the
wall variant of `kernels.ref.navier_stokes_rhs_planar`.  Pinned here, at
a CPU size (N = 3, 2 x 4 x 2 elements, 2 envs): the planar wall RHS
against the natural-layout `channel_rhs`, one RL interval planar against
natural, and that the periodic HIT instantiation of the shared body still
traces to the very program it traced to before the wall variant existed.
"""
import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cfd import channel
from repro.cfd.channel import ChannelConfig
from repro.cfd.solver import HITConfig
from repro.kernels import ops as kops
from repro.kernels import rhs as rhs_mod

pytestmark = pytest.mark.kernel_parity

CFG = ChannelConfig(n_elem=(2, 4, 2), use_kernels=False)


def _inputs(cfg, n_envs=2):
    bank = channel.make_state_bank(jax.random.PRNGKey(1), cfg, n_envs)
    kx, _, kz = cfg.n_elem
    sb, st = (jax.random.uniform(jax.random.PRNGKey(s), (n_envs, kx, kz),
                                 minval=0.5, maxval=1.5) for s in (2, 3))
    return bank, sb, st


@pytest.mark.parametrize("impl", ["ref", "kernel"])
def test_planar_wall_rhs_matches_channel_rhs(impl):
    """One RHS evaluation, jnp body ("ref") and Pallas kernel (interpret
    mode off the chip), against the natural-layout assembly.  Same
    equations, other summation orders (offset sums against einsums, the
    matching mean's weights), so they agree to float32 rounding: ~1e-6 of
    each channel's largest value (measured 1.1e-6), pinned at 2e-4 as the
    HIT fused RHS is (tests/test_kernel_parity.py)."""
    bank, sb, st = _inputs(CFG)
    ops = CFG.operators()
    n = CFG.n
    to_nodes = lambda s: jnp.broadcast_to(s[..., None, None], s.shape + (n, n))
    want = channel.channel_rhs(bank, to_nodes(sb), to_nodes(st), CFG, ops)
    x, scale, block_e = rhs_mod.to_planar_wall_batch(
        bank, channel.wall_scale_elements(sb, st, CFG))
    got = kops.wall_rhs_planar(x, scale, ops["D"], ops["w"], k=CFG.n_elem,
                               block_e=block_e, impl=impl,
                               **channel.wall_rhs_scalars(CFG, ops))
    got = rhs_mod.from_planar_batch(got, bank.shape)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_wall_flux_reads_the_action():
    """The wall-stress scales reach the RHS on the wall elements only: a
    different scale on the bottom wall changes the x-momentum RHS of the
    ky = 0 elements and leaves the interior elements' alone."""
    bank, sb, st = _inputs(CFG)
    ops = CFG.operators()
    kw = channel.wall_rhs_scalars(CFG, ops)

    def rhs(sb):
        x, scale, block_e = rhs_mod.to_planar_wall_batch(
            bank, channel.wall_scale_elements(sb, st, CFG))
        out = kops.wall_rhs_planar(x, scale, ops["D"], ops["w"],
                                   k=CFG.n_elem, block_e=block_e,
                                   impl="ref", **kw)
        return np.asarray(rhs_mod.from_planar_batch(out, bank.shape))

    a, b = rhs(sb), rhs(2.0 * sb)
    assert not np.array_equal(a[:, :, 0, ..., 1], b[:, :, 0, ..., 1])
    np.testing.assert_array_equal(a[:, :, 1:, ...], b[:, :, 1:, ...])


def test_planar_interval_matches_natural():
    """One RL interval (all substeps x 5 RK stages): the planar carry
    through the kernel (interpret mode) against the natural-layout carry
    through `channel_rhs`.  Rounding differences of ~1e-6 per RHS grow
    over the substeps; the env-step parity gate's tolerance (rtol 1e-3,
    atol 1e-4, tests/test_kernel_parity.py) holds them."""
    bank, sb, st = _inputs(CFG)
    on = dataclasses.replace(CFG, use_kernels=True)
    assert CFG.n_substeps > 1
    want = channel.advance_rl_interval(bank, sb, st, CFG)
    got = channel.advance_rl_interval(bank, sb, st, on)
    assert got.shape == bank.shape and got.dtype == jnp.float32
    assert not np.array_equal(np.asarray(got), np.asarray(bank))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=1e-4)


# sha256 of the jaxpr text of the periodic kernel call below, as it traced
# before the wall variant was added.  Identical jaxprs compile to identical
# programs, so the HIT kernel's output cannot move by a bit.  A JAX upgrade
# that changes how jaxprs print needs a new pin, taken from that version.
HIT_PLANAR_JAXPR_SHA256 = (
    "74939dd5f40ca4f2c94e8a356ea7fbd5f52d5b8e1a9c4d54e6bd5ae49896a672")


def test_periodic_hit_kernel_traces_unchanged():
    cfg = HITConfig(n_poly=3, n_elem=2, use_kernels=False)
    ops = cfg.operators()
    kw = dict(inv_w_end=ops["inv_w_end"], jac=cfg.dg.jac,
              delta=cfg.delta_filter, mu=cfg.gas.mu, prandtl=cfg.prandtl,
              prandtl_turb=cfg.prandtl_turb, forcing_a0=cfg.forcing_a0,
              k_tke=cfg.k_tke)
    u = (jax.random.normal(jax.random.PRNGKey(3), (5, 64, 32)) * 0.1
         + jnp.array([1.0, 0.3, 0.2, 0.1, 18.0])[:, None, None])
    cs = jnp.full((64, 32), 0.17)
    jaxpr = jax.make_jaxpr(
        lambda u, cs: rhs_mod.fused_navier_stokes_rhs_planar(
            u, cs, ops["D"], ops["w"], k=2, block_e=4, interpret=True,
            **kw))(u, cs)
    digest = hashlib.sha256(str(jaxpr).encode()).hexdigest()
    assert digest == HIT_PLANAR_JAXPR_SHA256
