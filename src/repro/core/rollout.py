"""Synchronous sharded rollout — the TPU-native replacement for Relexi's
SmartSim launch/poll loop (paper Algorithm 1, lines 4-13).

Where the paper starts `n_envs` MPI jobs and ping-pongs state/action tuples
through a Redis database, here the environment batch IS one array program:
the batch axis shards over the (pod, data) mesh axes, element space of each
environment optionally shards over `model`, and one `lax.scan` over the
episode replaces launch + polling — synchronization becomes the data
dependency between scan iterations.  "Launch overhead" is a single XLA
dispatch (benchmarks/launch_overhead.py quantifies this against the paper's
Sec. 3.3 numbers).

The scan is generic over any registered `Env` (envs/base.py): the env is a
static value closed over by jit, and `observe`/`step` are pure, so the same
function lowers the HIT-LES fleet and the 1-D Burgers fleet alike.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..envs.base import Env, EnvState
from . import policy as policy_lib
from .ppo import Trajectory


def rollout(
    params: dict,
    pcfg: policy_lib.PolicyConfig | None,
    env: Env,
    u0: jax.Array,
    key: jax.Array,
    *,
    deterministic: bool = False,
    policy: policy_lib.PolicyFns | None = None,
) -> Trajectory:
    """Roll a batch of environments for one full episode (T = env.n_actions).

    u0: (B, *state_shape) initial solver states (bank rows).
    Returns a time-major Trajectory (T, B, ...).

    `policy` optionally substitutes the whole policy callable bundle
    (e.g. a multi-scenario head from `fleet/multitask.py`); left None, the
    default single-scenario policy is bound from `pcfg`.

    The per-step action noise is pre-drawn OUTSIDE the scan (one
    `normal(key_t, (B,) + action_shape)` per step key — the same stream
    `PolicyFns.sample` would draw inside) and consumed as scan data.  This
    keeps the scan body structurally identical to the fleet's super-batch
    program (`fleet/superbatch.py`), whose padded batch must reproduce
    this path bit-for-bit on the real rows: drawing inside vs. feeding as
    data changes XLA's fusion (FMA) choices at the ulp level, so both
    paths draw the same way.
    """
    pol = policy if policy is not None else policy_lib.policy_fns(pcfg)
    n_steps = env.n_actions
    batch = u0.shape[0]
    state0 = EnvState(u=u0, t_step=jnp.zeros((batch,), jnp.int32))
    step_keys = jax.random.split(key, n_steps)
    noise = jax.vmap(
        lambda kk: jax.random.normal(kk, (batch,) + env.action_spec.shape)
    )(step_keys)

    def step_fn(state: EnvState, noise_t: jax.Array):
        obs = env.observe(state)
        if deterministic:
            action = pol.mean(params, obs)
            mean, std = pol.dist(params, obs)
            logp = policy_lib.log_prob(mean, std, action)
        else:
            mean, std = pol.dist(params, obs)
            action = mean + std * noise_t
            logp = policy_lib.log_prob(mean, std, action)
        val = pol.value(params, obs)
        res = env.step(state, action)
        out = (obs, action, logp, res.reward, res.done, val, res.reverted)
        return res.state, out

    final_state, (obs, actions, log_probs, rewards, dones, values,
                  reverted) = jax.lax.scan(step_fn, state0, noise)
    last_obs = env.observe(final_state)
    last_value = pol.value(params, last_obs)
    return Trajectory(
        obs=obs,
        actions=actions,
        log_probs=log_probs,
        rewards=rewards,
        dones=dones,
        values=values,
        last_value=last_value,
        reverted=reverted,
    )


def episode_return(traj: Trajectory) -> jax.Array:
    """Undiscounted per-environment episode return (B,)."""
    return jnp.sum(traj.rewards, axis=0)


def normalized_return(traj: Trajectory) -> jax.Array:
    """Return normalized by the maximum achievable (+1 per step), as Fig. 5."""
    return episode_return(traj) / traj.rewards.shape[0]


def constant_action_return(env: Env, u0: jax.Array, value: float) -> float:
    """Normalized episode return of a constant-action policy on initial
    states u0 (B, *state_shape) — the paper's static baselines (Fig. 5
    bottom: Smagorinsky C_s=0.17, implicit LES C_s=0), for any Env."""
    state = EnvState(u=u0, t_step=jnp.zeros((u0.shape[0],), jnp.int32))
    action = jnp.full((u0.shape[0],) + env.action_spec.shape, value,
                      jnp.float32)
    step = jax.jit(env.step)
    total = 0.0
    for _ in range(env.n_actions):
        res = step(state, action)
        state = res.state
        total += float(jnp.mean(res.reward))
    return total / env.n_actions
