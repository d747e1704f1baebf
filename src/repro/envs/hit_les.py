"""HIT-LES scenario (paper Sec. 5.2) on the generic Env protocol.

This is a thin, zero-cost adapter over the pure free functions in
`repro.cfd.env` — the numerics are byte-for-byte the pre-refactor HIT
environment (tests/test_envs.py pins the rollout arrays against a direct
composition of those free functions).  The adapter only declares the specs
and owns the synthetic-DNS reference spectrum that the reward compares
against (a numpy config-time constant, baked into the jitted step).

Observation channels (named, per `ObsSpec.channel_specs`): the three
velocity components ('u_x', 'u_y', 'u_z') at every element node, each
normalized by the forcing-scale rms velocity u_rms.

Registry overrides reach every `HITConfig` field, e.g.
`envs.make("hit_les_reduced", precision="bf16")` advances the flow state
in bfloat16 (obs/reward/PPO stay float32 — see HITConfig.precision), and
`use_kernels=True/False` forces the fused Pallas RHS path on or off.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..cfd import env as hit_kernel
from ..cfd import initial, spectra
from ..cfd.solver import HITConfig
from ..configs import relexi_hit
from .base import ActionSpec, EnvState, ObsSpec, StepResult, velocity_channels
from .registry import register


@dataclasses.dataclass(frozen=True)
class HITLESEnv:
    """Forced homogeneous isotropic turbulence LES, per-element C_s control."""

    cfg: HITConfig

    @property
    def obs_spec(self) -> ObsSpec:
        n = self.cfg.n_poly + 1
        return ObsSpec(n_elements=self.cfg.n_elem**3, spatial=(n, n, n),
                       channel_specs=velocity_channels(3, self.cfg.u_rms))

    @property
    def action_spec(self) -> ActionSpec:
        return ActionSpec(n_elements=self.cfg.n_elem**3, low=0.0,
                          high=self.cfg.cs_max)

    @property
    def n_actions(self) -> int:
        return self.cfg.n_actions

    def e_dns(self) -> jax.Array:
        """Synthetic DNS target spectrum (config-time constant)."""
        return jnp.asarray(spectra.reference_spectrum(self.cfg), jnp.float32)

    def initial_state_bank(self, key: jax.Array, n: int) -> jax.Array:
        return initial.make_state_bank(key, self.cfg, n)

    def reset_from_bank(self, bank: jax.Array, index: jax.Array
                        ) -> tuple[EnvState, jax.Array]:
        state, obs = hit_kernel.reset_from_bank(bank, index, self.cfg)
        return EnvState(*state), obs

    def observe(self, state: EnvState) -> jax.Array:
        return hit_kernel.observe(state.u, self.cfg)

    def step(self, state: EnvState, action: jax.Array) -> StepResult:
        res = hit_kernel.step(state, action, self.cfg, self.e_dns())
        return StepResult(EnvState(*res.state), res.obs, res.reward, res.done,
                          res.reverted)


@register("hit_les_24dof")
def _hit24(**overrides) -> HITLESEnv:
    """Paper Table 1, 24-DOF configuration (N=5, 4^3 elements)."""
    return HITLESEnv(cfg=dataclasses.replace(relexi_hit.HIT24, **overrides))


@register("hit_les_32dof")
def _hit32(**overrides) -> HITLESEnv:
    """Paper Table 1, 32-DOF configuration (N=7, 4^3 elements)."""
    return HITLESEnv(cfg=dataclasses.replace(relexi_hit.HIT32, **overrides))


@register("hit_les_reduced")
def _hit_reduced(**overrides) -> HITLESEnv:
    """CPU-friendly smoke scale (N=3, 2^3 elements, short episodes)."""
    return HITLESEnv(cfg=dataclasses.replace(relexi_hit.reduced(), **overrides))
