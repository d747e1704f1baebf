"""Ahead-of-time compiles of the main-path Pallas kernels for TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a described `v5e:2x2` topology, and refuses what Mosaic would refuse
on the chip (layouts it cannot lower, VMEM overruns) — which interpret-mode
parity tests cannot see.  Sizes are the paper's Table 1 meshes (24 and 32
DOF: N = 5 and 7, 4^3 elements) at a fleet of 16 environments.  Each test
asserts the compiled program holds the Mosaic kernel (`tpu_custom_call`).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.  Nothing runs on a device; these are compiles only.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import relexi_hit
from repro.kernels.dg_derivative import dg_derivative3
from repro.kernels.rhs import fused_navier_stokes_rhs
from repro.kernels.smagorinsky import smagorinsky_nut
from repro.kernels.wall_model import wall_model_tau

N_ENVS = 16
CONFIGS = {"24dof": relexi_hit.HIT24, "32dof": relexi_hit.HIT32}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dof", sorted(CONFIGS))
def test_fused_rhs_compiles_for_v5e(one_chip, dof):
    cfg = CONFIGS[dof]
    ops = cfg.operators()
    n, k = cfg.n_poly + 1, cfg.n_elem
    kw = dict(inv_w_end=ops["inv_w_end"], jac=cfg.dg.jac,
              delta=cfg.delta_filter, mu=cfg.gas.mu, prandtl=cfg.prandtl,
              prandtl_turb=cfg.prandtl_turb, forcing_a0=cfg.forcing_a0,
              k_tke=cfg.k_tke, interpret=False)
    mesh = (N_ENVS, k, k, k, n, n, n)
    compiled = _compile(
        lambda u, cs, d, w: fused_navier_stokes_rhs(u, cs, d, w, **kw),
        one_chip, mesh + (5,), mesh, (n, n), (n,))
    _assert_kernel(compiled)


@pytest.mark.parametrize("dof", sorted(CONFIGS))
def test_dg_derivative3_compiles_for_v5e(one_chip, dof):
    cfg = CONFIGS[dof]
    n = cfg.n_poly + 1
    compiled = _compile(lambda u, d: dg_derivative3(u, d, interpret=False),
                        one_chip, (N_ENVS * cfg.n_elem**3, n, n, n, 4),
                        (n, n))
    _assert_kernel(compiled)


@pytest.mark.parametrize("dof", sorted(CONFIGS))
def test_smagorinsky_nut_compiles_for_v5e(one_chip, dof):
    cfg = CONFIGS[dof]
    points = N_ENVS * cfg.n_elem**3 * (cfg.n_poly + 1) ** 3
    compiled = _compile(
        lambda g, cs: smagorinsky_nut(g, cs, cfg.delta_filter,
                                      interpret=False),
        one_chip, (points, 3, 3), (points,))
    _assert_kernel(compiled)


@pytest.mark.parametrize("dof", sorted(CONFIGS))
def test_wall_model_tau_compiles_for_v5e(one_chip, dof):
    cfg = CONFIGS[dof]
    # both walls of a K x K element face grid, n^2 face nodes each
    faces = (N_ENVS, 2 * cfg.n_elem**2, (cfg.n_poly + 1) ** 2)
    compiled = _compile(
        lambda u, r: wall_model_tau(u, r, y_m=0.05, nu=5e-3,
                                    interpret=False),
        one_chip, faces, faces)
    _assert_kernel(compiled)
