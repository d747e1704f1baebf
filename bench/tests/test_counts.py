"""bench/counts reads the work off the shapes of a call, never off the
code that does it: the fused kernel's call and the jnp reference's call
read the same operations and bytes."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from bench.counts import hit as counts


def _rhs_args(name, use_kernels, batch):
    """(u, cs_nodes) shapes that cfd/solver.navier_stokes_rhs hands its
    implementation, traced at the configuration's size."""
    from repro import envs
    from repro.cfd import solver

    cfg = dataclasses.replace(envs.make(name).cfg, use_kernels=use_kernels)
    n, k = cfg.n_poly + 1, cfg.n_elem
    u = jax.ShapeDtypeStruct((batch, k, k, k, n, n, n, 5), jnp.float32)
    seen = []

    def capture(u, cs):
        seen.append(u.shape)
        return solver.navier_stokes_rhs(u, cs, cfg, cfg.operators())

    cs = jax.ShapeDtypeStruct(u.shape[:-1], jnp.float32)
    jax.eval_shape(capture, u, cs)
    return seen[0]


@pytest.mark.parametrize("name,n", [("hit_les_24dof", 6),
                                    ("hit_les_32dof", 8)])
def test_fused_and_jnp_paths_read_the_same_counts(name, n):
    fused = counts.rhs_call_from_state(_rhs_args(name, True, 16))
    plain = counts.rhs_call_from_state(_rhs_args(name, False, 16))
    assert fused == plain
    nodes = 16 * 4**3 * n**3
    assert fused[0] == pytest.approx(counts.rhs_flops_per_node(n) * nodes)
    assert fused[1] == 16 * 64 * (n**3 * 40 + 4)


def test_per_node_count_at_paper_sizes():
    assert counts.rhs_flops_per_node(6) == pytest.approx(1140.5)
    assert counts.rhs_flops_per_node(8) == pytest.approx(1401.625)


def test_iteration_count_is_the_sum_of_its_parts():
    it = counts.train_iteration_flops(n=6, k=4, n_envs=64, n_actions=50,
                                      n_substeps=13, d_embed=32, n_shared=2)
    rhs, _ = counts.rhs_call(6, 4, 64)
    nodes = 64 * 64 * 216
    solver = 50 * 13 * 5 * (rhs + 25 * nodes)
    policy = 50 * 64 * 64 * counts.policy_forward_flops(648, 32, 2) * 16
    assert it == pytest.approx(solver + policy)
