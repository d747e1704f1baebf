"""The solver-agnostic environment contract.

The paper's framework ("Relexi is built with modularity in mind and allows
easy integration of various HPC solvers") couples ANY MPI solver to the RL
loop through a thin state/action/reward exchange.  This module is the
jit-native formulation of that boundary: an environment is a *hashable,
static* object whose methods are pure array programs, so the whole fleet —
any scenario — compiles into one XLA program (jit / vmap / shard_map pass
straight through).

Layout conventions shared by every environment:

  * `EnvState.u` is a single conservative/nodal state array whose leading
    axes may carry an environment batch; `initial_state_bank` returns a
    stack of such arrays with the bank axis first.
  * Observations are element-local: shape (..., E, *spatial, C) with E the
    number of DG elements and `spatial` the per-element node grid (1-D or
    3-D).  The trailing axis is NOT a bare count: every channel is declared
    by name in `ObsSpec.channel_specs` (a tuple of `ChannelSpec`), in the
    order `observe()` stacks them, each carrying the physical normalization
    scale the env already divided by.  `ObsSpec.channels` is the derived
    count.
  * Each observation channel arrives O(1): `observe()` divides channel c by
    `channel_specs[c].scale` (e.g. velocities by u_rms, wall pressure by
    the wall shear stress).  The training stack never re-applies the scale;
    it may apply the declared per-channel `gain` at the policy input
    (see core/policy.py).
  * Actions are per-element scalars (..., E) bounded to
    [`ActionSpec.low`, `ActionSpec.high`].

`core/policy.py` builds its actor/critic heads from these specs alone;
`core/rollout.py` scans `step` over any `Env`; `core/orchestrator.py` only
adds fleet sharding + the initial-state bank.  Nothing in `core/` imports a
concrete solver.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Protocol, runtime_checkable

import jax
import jax.numpy as jnp


class EnvState(NamedTuple):
    """Carried MDP state: solver field + RL step counter."""

    u: jax.Array          # solver state; leading axes may be a batch
    t_step: jax.Array     # RL step counter (int32, scalar or (B,))


class StepResult(NamedTuple):
    state: EnvState
    obs: jax.Array
    reward: jax.Array
    done: jax.Array
    reverted: jax.Array   # bool like `done`: the blow-up guard reverted it


@dataclasses.dataclass(frozen=True)
class ChannelSpec:
    """One named observation channel.

    `scale` is the physical divisor the env ALREADY applied to this channel
    inside `observe()` (e.g. u_rms for velocities, rho u_tau^2 for wall
    pressure), declared so consumers can un-normalize for diagnostics.  The
    training stack never re-applies it — channels arrive O(1) by contract.
    `gain` is an optional policy-input multiplier for channels whose O(1)
    normalization still leaves them systematically small/large next to
    their siblings; `core/policy.py` applies it at the trunk input.
    """

    name: str
    scale: float = 1.0
    gain: float = 1.0


@dataclasses.dataclass(frozen=True)
class ObsSpec:
    """Declarative per-environment observation layout (..., E, *spatial, C).

    The trailing axis is a tuple of NAMED channels, in the order `observe()`
    stacks them; the legacy `channels` count and uniform `scale` survive as
    derived properties.

    >>> spec = ObsSpec(n_elements=8, spatial=(4, 4, 4),
    ...                channel_specs=(ChannelSpec("u_x", scale=2.0),
    ...                               ChannelSpec("u_y", scale=2.0),
    ...                               ChannelSpec("u_z", scale=2.0)))
    >>> spec.channels
    3
    >>> spec.channel_names
    ('u_x', 'u_y', 'u_z')
    >>> spec.scale
    2.0
    >>> spec.shape
    (8, 4, 4, 4, 3)
    """

    n_elements: int                 # E: number of DG elements
    spatial: tuple[int, ...]        # per-element node grid, e.g. (n, n, n) or (n,)
    channel_specs: tuple[ChannelSpec, ...]

    def __post_init__(self):
        names = self.channel_names
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate channel names: {names}")

    @property
    def channels(self) -> int:
        """C — derived from the declared channel tuple (legacy accessor)."""
        return len(self.channel_specs)

    @property
    def channel_names(self) -> tuple[str, ...]:
        return tuple(c.name for c in self.channel_specs)

    @property
    def channel_scales(self) -> tuple[float, ...]:
        return tuple(c.scale for c in self.channel_specs)

    @property
    def channel_gains(self) -> tuple[float, ...]:
        return tuple(c.gain for c in self.channel_specs)

    @property
    def scale(self) -> float:
        """Legacy uniform scale; defined only when all channels agree."""
        scales = set(self.channel_scales)
        if len(scales) != 1:
            raise ValueError(
                f"mixed per-channel scales {self.channel_scales}; "
                "use channel_scales instead of the legacy uniform scale")
        return next(iter(scales))

    @property
    def shape(self) -> tuple[int, ...]:
        return (self.n_elements, *self.spatial, self.channels)

    @property
    def ndim_spatial(self) -> int:
        return len(self.spatial)

    def validate(self, obs) -> None:
        """Raise if `obs` does not conform to this spec (trailing axes;
        name uniqueness is already enforced at construction)."""
        got = tuple(obs.shape[-(len(self.shape)):])
        if got != self.shape:
            raise ValueError(f"observation trailing shape {got} != declared "
                             f"{self.shape} (channels {self.channel_names})")


def velocity_channels(ndim: int, scale: float) -> tuple[ChannelSpec, ...]:
    """The standard velocity channel block: ('u_x'[, 'u_y', 'u_z'])."""
    return tuple(ChannelSpec(f"u_{ax}", scale=scale)
                 for ax in ("x", "y", "z")[:ndim])


@dataclasses.dataclass(frozen=True)
class ActionSpec:
    """Per-element bounded scalar action (..., E) in [low, high]."""

    n_elements: int
    low: float = 0.0
    high: float = 1.0

    @property
    def shape(self) -> tuple[int, ...]:
        return (self.n_elements,)


@runtime_checkable
class Env(Protocol):
    """The contract every registered scenario implements.

    Implementations must be hashable (frozen dataclasses over scalar
    configs) — they are closed over by jit as static values — and every
    method must be a pure function of its array arguments.
    """

    @property
    def obs_spec(self) -> ObsSpec: ...

    @property
    def action_spec(self) -> ActionSpec: ...

    @property
    def n_actions(self) -> int:
        """Episode horizon T (fixed-length episodes, as in the paper)."""
        ...

    def initial_state_bank(self, key: jax.Array, n: int) -> jax.Array:
        """(n, *state_shape) device-resident bank of initial solver states."""
        ...

    def reset_from_bank(self, bank: jax.Array, index: jax.Array
                        ) -> tuple[EnvState, jax.Array]: ...

    def observe(self, state: EnvState) -> jax.Array: ...

    def step(self, state: EnvState, action: jax.Array) -> StepResult:
        """One MDP transition; deterministic given (state, action)."""
        ...


def init_state(u0: jax.Array, batch_shape: tuple[int, ...] = ()) -> EnvState:
    """Wrap bank rows (or a single state) into a fresh EnvState at t=0."""
    return EnvState(u=u0, t_step=jnp.zeros(batch_shape, jnp.int32))


def as_env(env_or_cfg) -> Env:
    """Coerce legacy `HITConfig` values to the Env protocol.

    Pre-refactor call sites passed a raw `HITConfig` into the orchestrator /
    runner; keep them working by wrapping it in the HIT-LES adapter.
    """
    from ..cfd.solver import HITConfig
    if isinstance(env_or_cfg, HITConfig):
        from .hit_les import HITLESEnv
        return HITLESEnv(cfg=env_or_cfg)
    return env_or_cfg
