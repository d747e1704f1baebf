"""Forced 1-D Burgers control scenario on the generic Env protocol.

Proves the env abstraction end-to-end: a completely different solver
(1-D Burgers DGSEM, per-element eddy-viscosity control, 1-D specs) trains
through the *unchanged* runner/orchestrator/rollout/PPO stack that the
3-D HIT-LES scenario uses.  See cfd/burgers1d.py for the physics.

Observation channels (named, per `ObsSpec.channel_specs`): the single
scalar field 'u' at every element node, normalized by the forcing-scale
rms velocity u_rms.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..cfd import burgers1d, spectra
from ..cfd.burgers1d import BurgersConfig
from .base import ActionSpec, ChannelSpec, EnvState, ObsSpec, StepResult
from .registry import register


@dataclasses.dataclass(frozen=True)
class BurgersEnv:
    """Forced viscous Burgers LES, per-element eddy-viscosity control."""

    cfg: BurgersConfig

    @property
    def obs_spec(self) -> ObsSpec:
        return ObsSpec(n_elements=self.cfg.n_elem, spatial=(self.cfg.n,),
                       channel_specs=(ChannelSpec("u", scale=self.cfg.u_rms),))

    @property
    def action_spec(self) -> ActionSpec:
        return ActionSpec(n_elements=self.cfg.n_elem, low=0.0,
                          high=self.cfg.c_max)

    @property
    def n_actions(self) -> int:
        return self.cfg.n_actions

    def e_ref(self) -> jax.Array:
        """Synthetic k^-2 target spectrum (config-time constant)."""
        return jnp.asarray(burgers1d.reference_spectrum(self.cfg), jnp.float32)

    def initial_state_bank(self, key: jax.Array, n: int) -> jax.Array:
        return burgers1d.make_state_bank(key, self.cfg, n)

    def reset_from_bank(self, bank: jax.Array, index: jax.Array
                        ) -> tuple[EnvState, jax.Array]:
        u = jnp.take(bank, index, axis=0)
        state = EnvState(u=u, t_step=jnp.zeros((), jnp.int32))
        return state, self.observe(state)

    def observe(self, state: EnvState) -> jax.Array:
        return state.u / self.cfg.u_rms

    def step(self, state: EnvState, action: jax.Array) -> StepResult:
        """One MDP transition with the same in-graph blow-up guard as the
        HIT scenario: a non-finite advance reverts the state and floors the
        reward at -1 (see cfd/env.py for the rationale)."""
        cfg = self.cfg
        c_elem = jnp.clip(action, 0.0, cfg.c_max)
        u_next = burgers1d.advance_rl_interval(state.u, c_elem, cfg)
        finite = jnp.all(jnp.isfinite(u_next),
                         axis=tuple(range(u_next.ndim - 3, u_next.ndim)))
        u_next = jnp.where(finite[..., None, None, None], u_next, state.u)
        e_les = burgers1d.les_spectrum(u_next, cfg)
        ell = spectra.spectral_error(e_les, self.e_ref(), cfg.k_max)
        reward = jnp.where(finite, spectra.reward_from_error(ell, cfg.alpha),
                           -1.0)
        t_next = state.t_step + 1
        done = t_next >= cfg.n_actions
        next_state = EnvState(u=u_next, t_step=t_next)
        return StepResult(next_state, self.observe(next_state), reward, done,
                          ~finite)


@register("burgers_96dof")
def _burgers96(**overrides) -> BurgersEnv:
    """Production scale: N=7, 12 elements (96 DOF), full-length episodes."""
    return BurgersEnv(cfg=BurgersConfig(**overrides))


@register("burgers_reduced")
def _burgers_reduced(**overrides) -> BurgersEnv:
    """CPU-friendly smoke scale: N=3, 4 elements, short episodes."""
    defaults = dict(n_poly=3, n_elem=4, nu=2e-2, k_max=3, alpha=0.4,
                    t_end=0.3, dt_rl=0.1, k_eta=6.0)
    defaults.update(overrides)
    return BurgersEnv(cfg=BurgersConfig(**defaults))
