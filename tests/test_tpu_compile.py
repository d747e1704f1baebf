"""Ahead-of-time compiles of the main-path Pallas kernels for TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a described `v5e:2x2` topology, and refuses what Mosaic would refuse
on the chip (layouts it cannot lower, VMEM overruns) — which interpret-mode
parity tests cannot see.  Sizes are the paper's Table 1 meshes (24 and 32
DOF: N = 5 and 7, 4^3 elements) at a fleet of 16 environments.  Each test
asserts the compiled program holds the Mosaic kernel (`tpu_custom_call`).
The wall-modelled channel's `fused_wall_rhs` is compiled at its Re_tau
2000 block (N = 5, 8 x 8 x 4 elements: 256 lanes per mesh).  The RL
interval (`solver.advance_rl_interval` and `channel.advance_rl_interval`
with kernels on) is compiled whole, to check that its RK loop runs on the
kernel's planar layout.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.  Nothing runs on a device; these are compiles only.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.cfd import channel, solver
from repro.configs import channel_wmles, relexi_hit
from repro.kernels import policy
from repro.kernels.dg_derivative import dg_derivative3
from repro.kernels.rhs import fused_navier_stokes_rhs, fused_wall_rhs_planar
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


def test_fused_wall_rhs_compiles_for_v5e(one_chip):
    """The wall-modelled channel's kernel (Re_tau 2000: N = 5 on 8 x 8 x 4
    elements) at its production block, one 256-lane mesh per grid step."""
    cfg = channel_wmles.RE2000
    ops = cfg.operators()
    n, lanes = cfg.n, N_ENVS * 256
    kw = dict(k=cfg.n_elem, block_e=1, interpret=False,
              **channel.wall_rhs_scalars(cfg, ops))
    compiled = _compile(
        lambda u, s, d, w: fused_wall_rhs_planar(u, s, d, w, **kw),
        one_chip, (5, n**3, lanes), (1, lanes), (n, n), (n,))
    _assert_kernel(compiled)
    assert "fused_wall_rhs" in compiled.as_text()


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


def _computations(text: str) -> dict[str, list[str]]:
    """{computation name: its instruction lines} of an HLO module's text."""
    comps, cur = {}, None
    for line in text.splitlines():
        m = re.match(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$", line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif cur is not None and "=" in line:
            cur.append(line.split(", metadata=")[0])
    return comps


def _reachable(comps: dict, root: str) -> list[str]:
    """Instruction lines of `root` and of every computation it calls."""
    out, todo, seen = [], [root], set()
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        for line in comps.get(name, []):
            out.append(line)
            todo += re.findall(r"(?:calls|body|condition|to_apply)=%([\w.\-]+)",
                               line)
    return out


def _assert_planar_rk_loop(compiled):
    """The substep loop holds the Mosaic kernel once per RK stage and no
    array of the natural layout's rank (the 8-D state, its 7-D
    coefficients, their transposes): the relayouts stay at the interval's
    boundary, outside the RK loop."""
    comps = _computations(compiled.as_text())
    bodies = [b for lines in comps.values() for line in lines
              if " while(" in line
              for b in re.findall(r"body=%([\w.\-]+)", line)]
    assert len(bodies) == 1     # the substep scan
    body = _reachable(comps, bodies[0])
    assert sum("tpu_custom_call" in line for line in body) == 5  # RK stages
    ranks = {len(dims.split(",")) for line in body
             for dims in re.findall(r"\b[a-z]+[0-9]*\[([0-9,]+)\]", line)}
    assert max(ranks) < 7, sorted(ranks)


@pytest.mark.parametrize("dof", sorted(CONFIGS))
def test_rl_interval_steps_planar_carry_on_v5e(one_chip, dof, monkeypatch):
    """`solver.advance_rl_interval` steps a planar carry (HIT)."""
    # the kernel as on the chip: compiled, not interpreted (the backend
    # seen here is the CPU)
    monkeypatch.setattr(policy, "default_interpret", lambda: False)
    cfg = dataclasses.replace(CONFIGS[dof], use_kernels=True)
    n, k = cfg.n_poly + 1, cfg.n_elem
    _assert_planar_rk_loop(_compile(
        lambda u, cs: solver.advance_rl_interval(u, cs, cfg), one_chip,
        (N_ENVS, k, k, k, n, n, n, 5), (N_ENVS, k, k, k)))


def test_channel_rl_interval_steps_planar_carry_on_v5e(one_chip,
                                                       monkeypatch):
    """`channel.advance_rl_interval` steps a planar carry through
    `fused_wall_rhs` (Re_tau 2000 channel, 16 envs)."""
    monkeypatch.setattr(policy, "default_interpret", lambda: False)
    cfg = dataclasses.replace(channel_wmles.RE2000, use_kernels=True)
    n, (kx, ky, kz) = cfg.n, cfg.n_elem
    _assert_planar_rk_loop(_compile(
        lambda u, sb, st: channel.advance_rl_interval(u, sb, st, cfg),
        one_chip, (N_ENVS, kx, ky, kz, n, n, n, 5), (N_ENVS, kx, kz),
        (N_ENVS, kx, kz)))
