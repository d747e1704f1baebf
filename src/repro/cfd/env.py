"""The HIT LES reinforcement-learning environment (paper Sec. 5.2).

State  : coarse-scale conservative flow field on the DG mesh.
Obs    : per-element velocity nodal values, (K^3, n, n, n, 3), u_rms-normalized.
Action : per-element Smagorinsky coefficient C_s in [0, cs_max], (K^3,).
Reward : paper Eqs. (4)-(5) against the reference spectrum.

Pure-functional API (reset/step are jit/vmap/shard_map friendly); batching over
environments is done OUTSIDE by the orchestrator — mirroring the paper where
each FLEXI instance is an independent MPI job.

These free functions are the HIT *kernel*; the generic training stack talks
to them through the solver-agnostic adapter `repro.envs.hit_les.HITLESEnv`
(`envs.make("hit_les_24dof")`), which pins these numerics bit-for-bit.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import initial, solver, spectra
from .equations import conservative_to_primitive
from .solver import HITConfig


class EnvState(NamedTuple):
    u: jax.Array          # conservative nodal state (K,K,K,n,n,n,5)
    t_step: jax.Array     # RL step counter (int32 scalar)


class StepResult(NamedTuple):
    state: EnvState
    obs: jax.Array
    reward: jax.Array
    done: jax.Array
    reverted: jax.Array   # bool: the blow-up guard reverted the transition


def observe(u: jax.Array, cfg: HITConfig) -> jax.Array:
    """Element-local observations: (..., K^3, n, n, n, 3)."""
    _, vel, _, _ = conservative_to_primitive(u)
    batch = vel.shape[: vel.ndim - 7]
    k, n = cfg.n_elem, cfg.n_poly + 1
    obs = vel.reshape(batch + (k**3, n, n, n, 3))
    return obs / cfg.u_rms


def reset_from_bank(bank: jax.Array, index: jax.Array, cfg: HITConfig) -> tuple[EnvState, jax.Array]:
    """Initialize from state `index` of the device-resident bank."""
    u = jnp.take(bank, index, axis=0)
    state = EnvState(u=u, t_step=jnp.zeros((), jnp.int32))
    return state, observe(u, cfg)


def reset_random(key: jax.Array, cfg: HITConfig) -> tuple[EnvState, jax.Array]:
    u = initial.sample_initial_state(key, cfg)
    state = EnvState(u=u, t_step=jnp.zeros((), jnp.int32))
    return state, observe(u, cfg)


def step(state: EnvState, action: jax.Array, cfg: HITConfig,
         e_dns: jax.Array) -> StepResult:
    """One MDP transition: apply per-element C_s, advance Delta t_RL, reward.

    Solver blow-up guard (production fault tolerance): if the advanced state
    goes non-finite — an under-resolved LES with an exploratory C_s CAN blow
    up, the CFD analog of a crashed FLEXI instance — the transition reverts
    to the previous state and the agent receives the reward floor (-1).
    The episode stays finite, the penalty is learnable, and NaN never
    reaches the gradient (the paper's framework restarts the MPI job; here
    recovery is in-graph)."""
    cs = jnp.clip(action, 0.0, cfg.cs_max).reshape(
        action.shape[:-1] + (cfg.n_elem,) * 3
    )
    u_next = solver.advance_rl_interval(state.u, cs, cfg)
    finite = jnp.all(jnp.isfinite(u_next),
                     axis=tuple(range(u_next.ndim - 7, u_next.ndim)))  # (...,)
    u_next = jnp.where(finite[..., None, None, None, None, None, None, None],
                       u_next, state.u)
    e_les = spectra.les_spectrum(u_next, cfg)
    ell = spectra.spectral_error(e_les, e_dns, cfg.k_max)
    reward = jnp.where(finite, spectra.reward_from_error(ell, cfg.alpha), -1.0)
    t_next = state.t_step + 1
    done = t_next >= cfg.n_actions
    next_state = EnvState(u=u_next, t_step=t_next)
    return StepResult(next_state, observe(u_next, cfg), reward, done,
                      ~finite)
