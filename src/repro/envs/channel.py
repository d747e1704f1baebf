"""Wall-modeled channel-flow control scenario on the generic Env protocol.

The third registered scenario, and the first NON-PERIODIC one: it proves the
Env protocol carries an anisotropic state layout (Kx != Ky != Kz elements,
unequal box lengths) and weak wall boundary conditions end to end through
the unchanged orchestrator/rollout/runner.  See cfd/channel.py for the
physics (mixed-BC DGSEM, Reichardt wall model, pressure-gradient forcing).

Obs    : the two layers of wall-adjacent elements.  Channels are declared
         by name in `ObsSpec.channel_specs`:
           * base `channel_wm`: ('u_x', 'u_y', 'u_z') velocity nodes,
             normalized by u_bulk — (2*Kx*Kz, n, n, n, 3);
           * `channel_wm_p` (obs_pressure=True): the same three plus
             'p_wall', the near-wall static-pressure fluctuation p - p0
             normalized by the wall shear stress rho u_tau^2 —
             (2*Kx*Kz, n, n, n, 4);
           * `channel_wm_t` (obs_temperature=True): the same three plus
             'T_wall', the near-wall temperature fluctuation T - T0
             normalized by the friction-temperature scale u_tau^2/cp;
           * `channel_wm_hre`: the base observation at a higher-Re_tau
             configuration (Re_tau ~ 90, scaled Reichardt parameters).
         Top-wall elements are mirrored (y node axis flipped, v_y negated)
         so both walls present the same orientation to the shared policy
         trunk — "away from the wall" is always increasing node index.
Action : per-wall-element wall-stress scaling a in [0, a_max]; a = 1
         applies the equilibrium wall model as-is (the static baseline).
Reward : 2 exp(-l/alpha) - 1 with l the quadrature-weighted relative L2
         error of the x-z mean velocity profile against the Reichardt
         log-law reference — the profile analog of the paper's spectral
         reward.

Registry overrides reach every `ChannelConfig` field, e.g.
`envs.make("channel_wm", precision="bf16")` advances the flow state in
bfloat16 (obs/reward/PPO stay float32 — see ChannelConfig.precision).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..cfd import channel, spectra
from ..cfd.channel import ChannelConfig
from .base import (ActionSpec, ChannelSpec, EnvState, ObsSpec, StepResult,
                   velocity_channels)
from .registry import register


@dataclasses.dataclass(frozen=True)
class ChannelEnv:
    """Plane-channel WMLES, per-wall-element stress-scaling control.

    With `obs_pressure=True` the observation gains a fourth named channel:
    the near-wall pressure fluctuation normalized by rho u_tau^2 (the RL
    analog of HydroGym/drlfoam-style multi-field probes).  Its declared
    policy-input gain of 0.5 re-balances the channel against the O(1)
    velocities (p'_rms ~ 2-3 tau_w in channel flow).

    With `obs_temperature=True` the observation instead/additionally gains
    the near-wall temperature fluctuation T - T0 normalized by the
    friction-temperature scale u_tau^2/cp (`ChannelConfig.t_tau`) — the
    thermal sibling of the pressure channel (ROADMAP follow-on from the
    named-channel refactor).  Channel order is always
    velocities [, p_wall][, T_wall].
    """

    cfg: ChannelConfig
    obs_pressure: bool = False
    obs_temperature: bool = False

    @property
    def obs_spec(self) -> ObsSpec:
        n = self.cfg.n
        chans = velocity_channels(3, self.cfg.u_bulk)
        if self.obs_pressure:
            chans = chans + (ChannelSpec("p_wall", scale=self.cfg.tau_wall,
                                         gain=0.5),)
        if self.obs_temperature:
            chans = chans + (ChannelSpec("T_wall", scale=self.cfg.t_tau,
                                         gain=0.5),)
        return ObsSpec(n_elements=self.cfg.n_wall_elements,
                       spatial=(n, n, n), channel_specs=chans)

    @property
    def action_spec(self) -> ActionSpec:
        return ActionSpec(n_elements=self.cfg.n_wall_elements, low=0.0,
                          high=self.cfg.a_max)

    @property
    def n_actions(self) -> int:
        return self.cfg.n_actions

    def u_ref(self) -> jax.Array:
        """Reference mean profile (config-time constant, baked into step)."""
        return jnp.asarray(channel.reference_profile(self.cfg), jnp.float32)

    def initial_state_bank(self, key: jax.Array, n: int) -> jax.Array:
        return channel.make_state_bank(key, self.cfg, n)

    def reset_from_bank(self, bank: jax.Array, index: jax.Array
                        ) -> tuple[EnvState, jax.Array]:
        u = jnp.take(bank, index, axis=0)
        state = EnvState(u=u, t_step=jnp.zeros((), jnp.int32))
        return state, self.observe(state)

    def observe(self, state: EnvState) -> jax.Array:
        """Named-channel near-wall observation, both walls mirrored into the
        same orientation (cfd/channel.py wall_*_observation): velocities
        over u_bulk, plus the p_wall fluctuation over tau_wall when
        `obs_pressure` — (..., 2*Kx*Kz, n, n, n, C)."""
        obs = channel.wall_velocity_observation(state.u, self.cfg)
        obs = obs / self.cfg.u_bulk
        if self.obs_pressure:
            p = channel.wall_pressure_observation(state.u, self.cfg)
            obs = jnp.concatenate([obs, p / self.cfg.tau_wall], axis=-1)
        if self.obs_temperature:
            t = channel.wall_temperature_observation(state.u, self.cfg)
            obs = jnp.concatenate([obs, t / self.cfg.t_tau], axis=-1)
        return obs

    def _split_action(self, action: jax.Array
                      ) -> tuple[jax.Array, jax.Array]:
        """(..., 2*Kx*Kz) -> per-wall (..., Kx, Kz) scaling fields."""
        cfg = self.cfg
        kx, _, kz = cfg.n_elem
        a = jnp.clip(action, 0.0, cfg.a_max)
        grid = a.shape[:-1] + (kx, kz)
        bot = a[..., : kx * kz].reshape(grid)
        top = a[..., kx * kz:].reshape(grid)
        return bot, top

    def step(self, state: EnvState, action: jax.Array) -> StepResult:
        """One MDP transition with the shared in-graph blow-up guard: a
        non-finite advance reverts the state and floors the reward at -1
        (see cfd/env.py for the rationale)."""
        cfg = self.cfg
        scale_bot, scale_top = self._split_action(action)
        u_next = channel.advance_rl_interval(state.u, scale_bot, scale_top,
                                             cfg)
        finite = jnp.all(jnp.isfinite(u_next),
                         axis=tuple(range(u_next.ndim - 7, u_next.ndim)))
        u_next = jnp.where(
            finite[..., None, None, None, None, None, None, None],
            u_next, state.u)
        ops = cfg.operators()
        prof = channel.mean_velocity_profile(u_next, cfg, ops)
        ell = channel.profile_error(prof, self.u_ref(), ops)
        reward = jnp.where(finite,
                           spectra.reward_from_error(ell, cfg.alpha), -1.0)
        t_next = state.t_step + 1
        done = t_next >= cfg.n_actions
        next_state = EnvState(u=u_next, t_step=t_next)
        return StepResult(next_state, self.observe(next_state), reward, done,
                          ~finite)


@register("channel_wm")
def _channel_wm(**overrides) -> ChannelEnv:
    """Default scale: N=3, 3x4x3 elements, full-length episodes."""
    return ChannelEnv(cfg=ChannelConfig(**overrides))


@register("channel_wm_reduced")
def _channel_reduced(**overrides) -> ChannelEnv:
    """CPU-friendly smoke scale: 2x3x2 elements, short episodes."""
    defaults = dict(n_elem=(2, 3, 2), t_end=0.3, dt_rl=0.1)
    defaults.update(overrides)
    return ChannelEnv(cfg=ChannelConfig(**defaults))


@register("channel_wm_p")
def _channel_wm_p(**overrides) -> ChannelEnv:
    """4-channel variant: velocity + near-wall pressure observations."""
    return ChannelEnv(cfg=ChannelConfig(**overrides), obs_pressure=True)


@register("channel_wm_p_reduced")
def _channel_wm_p_reduced(**overrides) -> ChannelEnv:
    """CPU-friendly smoke scale of the 4-channel pressure variant."""
    defaults = dict(n_elem=(2, 3, 2), t_end=0.3, dt_rl=0.1)
    defaults.update(overrides)
    return ChannelEnv(cfg=ChannelConfig(**defaults), obs_pressure=True)


# Higher-Re_tau configuration: lower viscosity + higher target friction
# velocity push the matching point deep into the log layer (Re_tau =
# u_tau h / nu: 90 vs. the base 24), so the Reichardt inversion works at
# larger y+ — the fixed-point budget is scaled up with it (the "scaled
# Reichardt parameters" of the config family), and the initial
# perturbation amplitude rises to trip the stiffer profile.
_HRE = dict(nu=2e-3, u_tau=0.18, wm_iters=12, perturb=0.1)


@register("channel_wm_hre")
def _channel_wm_hre(**overrides) -> ChannelEnv:
    """Higher-Re_tau variant of `channel_wm` (Re_tau ~ 90)."""
    defaults = dict(_HRE)
    defaults.update(overrides)
    return ChannelEnv(cfg=ChannelConfig(**defaults))


@register("channel_wm_hre_reduced")
def _channel_wm_hre_reduced(**overrides) -> ChannelEnv:
    """CPU-friendly smoke scale of the higher-Re_tau variant."""
    defaults = dict(_HRE, n_elem=(2, 3, 2), t_end=0.3, dt_rl=0.1)
    defaults.update(overrides)
    return ChannelEnv(cfg=ChannelConfig(**defaults))


@register("channel_wm_t")
def _channel_wm_t(**overrides) -> ChannelEnv:
    """4-channel variant: velocity + near-wall temperature observations."""
    return ChannelEnv(cfg=ChannelConfig(**overrides), obs_temperature=True)


@register("channel_wm_t_reduced")
def _channel_wm_t_reduced(**overrides) -> ChannelEnv:
    """CPU-friendly smoke scale of the temperature variant."""
    defaults = dict(n_elem=(2, 3, 2), t_end=0.3, dt_rl=0.1)
    defaults.update(overrides)
    return ChannelEnv(cfg=ChannelConfig(**defaults), obs_temperature=True)
