"""Algorithmic operation and byte counts of the HIT-LES training step.

Every count follows the mathematics of the discretization and the shapes of
a call, never the code that implements it, so a faster implementation of
the same work reads the same count.  A floating-point operation is one add,
multiply, divide, square root, abs or max.  Per-node counts use n GLL nodes
per direction; face quantities are evaluated once per face point, and there
are 1/n face points per node per direction (periodic mesh).

The RHS per node (see `rhs_flops_per_node`):
  primitives                    15
  BR1 gradient, 4 fields x 3 d  12 (2n + 1 + 8/n)
  Smagorinsky nu_t              29
  per direction (x 3)           38n + 56 + 87/n:
      split-form KG volume      30n + 5  (20 per two-point flux + 10 to
                                          contract 5 channels, n partners)
      advective nodal flux      6
      LLF face flux             37/n
      viscous flux              31
      viscous volume deriv      8n       (4 non-zero channels)
      viscous face flux         8/n
      differences, lifts, jac   14 + 42/n
  Lundgren forcing              29
Minimal HBM bytes of one call: u read and the RHS written (5 channels each)
at the call's item size, and one C_s per element.
"""
from __future__ import annotations

RK_STAGES = 5
RK_UPDATE_FLOPS = 25       # per node and stage: du = a du + dt r; u += b du


def rhs_flops_per_node(n: int) -> float:
    return 138.0 * n + 253.0 + 357.0 / n


def rhs_call(n: int, k: int, batch: int, itemsize: int = 4
             ) -> tuple[float, float]:
    """(flops, minimal HBM bytes) of one RHS evaluation of `batch` meshes
    of k^3 elements with n^3 nodes each."""
    nodes = batch * k**3 * n**3
    return (rhs_flops_per_node(n) * nodes,
            float(nodes * 10 * itemsize + batch * k**3 * itemsize))


def rhs_call_from_state(shape: tuple[int, ...], itemsize: int = 4
                        ) -> tuple[float, float]:
    """The same, read off a state of shape (..., K, K, K, n, n, n, 5)."""
    k, n = shape[-7], shape[-4]
    batch = 1
    for s in shape[:-7]:
        batch *= s
    return rhs_call(n, k, batch, itemsize)


def policy_forward_flops(in_features: int, d_embed: int, n_shared: int
                         ) -> float:
    """Actor and critic on one element: adapter, shared layers, head."""
    one = 2 * in_features * d_embed + n_shared * 2 * d_embed**2 + 2 * d_embed
    return 2.0 * one


def train_iteration_flops(*, n: int, k: int, n_envs: int, n_actions: int,
                          n_substeps: int, d_embed: int, n_shared: int,
                          n_epochs: int = 5) -> float:
    """One fleet iteration: the rollout (RHS calls, RK updates, the policy
    on every element of every step) and the PPO update (forward and
    backward, three forwards' worth, per epoch over the whole batch)."""
    calls = n_actions * n_substeps * RK_STAGES
    rhs, _ = rhs_call(n, k, n_envs)
    nodes = n_envs * k**3 * n**3
    solver = calls * (rhs + RK_UPDATE_FLOPS * nodes)
    samples = n_actions * n_envs * k**3
    pol = policy_forward_flops(3 * n**3, d_embed, n_shared)
    return solver + samples * pol * (1 + 3 * n_epochs)
