"""Clip-PPO (Schulman et al. 2017) with GAE — the paper's RL algorithm.

Synchronous on-policy training exactly as in the paper (Sec. 5.3): sample a
batch of complete episodes with the current policy, then run `n_epochs`
gradient-ascent passes over the collected trajectories.  Hyperparameters
default to the paper's: gamma=0.995, lr=1e-4, Adam, 5 epochs, clip 0.2,
entropy coefficient 0.

Trajectories are laid out time-major:  (T, B, ...) with B the environment
batch — B is the axis that shards over the (pod, data) mesh axes (the
paper's "number of parallel FLEXI instances").
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from .. import optim
from . import policy as policy_lib


@dataclasses.dataclass(frozen=True)
class PPOConfig:
    gamma: float = 0.995          # paper Sec. 5.3
    lam: float = 0.95             # GAE lambda (TF-Agents default)
    clip: float = 0.2             # paper Sec. 5.3
    entropy_coef: float = 0.0     # paper Sec. 5.3
    value_coef: float = 0.5
    n_epochs: int = 5             # paper Sec. 5.3
    lr: float = 1e-4              # paper Sec. 5.3
    grad_clip: float | None = 1.0
    normalize_advantages: bool = True

    @property
    def adam(self) -> optim.AdamConfig:
        return optim.AdamConfig(lr=self.lr, grad_clip=self.grad_clip)


class Trajectory(NamedTuple):
    """Time-major rollout batch.  obs includes s_0..s_{T-1}; bootstrap value
    closes the episode (envs here terminate at fixed T, so last_value matters
    only for truncation handling; paper episodes end at t_end -> treat as
    terminal: done[-1] = True)."""

    obs: jax.Array        # (T, B, E, n, n, n, C)
    actions: jax.Array    # (T, B, E)
    log_probs: jax.Array  # (T, B)
    rewards: jax.Array    # (T, B)
    dones: jax.Array      # (T, B) bool, True where episode TERMINATES at t
    values: jax.Array     # (T, B) V(s_t) under the behavior policy
    last_value: jax.Array  # (B,) V(s_T)
    # (T, B) bool: the env's blow-up guard reverted step t (rollouts set
    # it; the update only counts it)
    reverted: jax.Array | None = None


def gae(traj: Trajectory, gamma: float, lam: float) -> tuple[jax.Array, jax.Array]:
    """Generalized advantage estimation; returns (advantages, returns), (T, B).

    delta_t = r_{t+1} + gamma V(s_{t+1}) (1-done) - V(s_t)
    A_t     = delta_t + gamma lam (1-done) A_{t+1}
    """
    not_done = 1.0 - traj.dones.astype(jnp.float32)
    next_values = jnp.concatenate(
        [traj.values[1:], traj.last_value[None]], axis=0
    )
    deltas = traj.rewards + gamma * next_values * not_done - traj.values

    def back(carry, x):
        delta, nd = x
        adv = delta + gamma * lam * nd * carry
        return adv, adv

    _, advs = jax.lax.scan(back, jnp.zeros_like(deltas[-1]), (deltas, not_done),
                           reverse=True)
    returns = advs + traj.values
    return advs, returns


def flatten_batch(traj: Trajectory, advantages: jax.Array,
                  returns: jax.Array, *, normalize: bool
                  ) -> tuple[jax.Array, ...]:
    """Flatten a time-major batch to the (T*B) minibatch tensors
    (obs, actions, log_probs, advantages, returns), optionally normalizing
    the advantages — shared by the single-scenario epoch below and the
    fleet's per-scenario joint update (fleet/multitask.py), so PPO
    preprocessing has one source of truth."""
    flat = jax.tree.map(
        lambda x: x.reshape((-1,) + x.shape[2:]),
        (traj.obs, traj.actions, traj.log_probs, advantages, returns),
    )
    obs_f, act_f, lp_f, adv_f, ret_f = flat
    if normalize:
        adv_f = (adv_f - jnp.mean(adv_f)) / (jnp.std(adv_f) + 1e-8)
    return obs_f, act_f, lp_f, adv_f, ret_f


def ppo_loss(
    params: dict,
    cfg: PPOConfig,
    pcfg: policy_lib.PolicyConfig | None,
    obs: jax.Array,
    actions: jax.Array,
    old_log_probs: jax.Array,
    advantages: jax.Array,
    returns: jax.Array,
    *,
    policy: policy_lib.PolicyFns | None = None,
) -> tuple[jax.Array, dict]:
    """Clipped surrogate + value loss + entropy bonus on a flat minibatch.

    `policy` optionally substitutes the policy callable bundle (the
    multi-scenario heads); left None it is bound from `pcfg`, which keeps
    the loss graph bit-identical to the pre-adapter path.
    """
    pol = policy if policy is not None else policy_lib.policy_fns(pcfg)
    mean, std = pol.dist(params, obs)
    new_log_probs = policy_lib.log_prob(mean, std, actions)
    ratio = jnp.exp(new_log_probs - old_log_probs)
    clipped = jnp.clip(ratio, 1.0 - cfg.clip, 1.0 + cfg.clip)
    surrogate = -jnp.mean(jnp.minimum(ratio * advantages, clipped * advantages))

    values = pol.value(params, obs)
    value_loss = 0.5 * jnp.mean((values - returns) ** 2)

    ent = jnp.mean(policy_lib.entropy(std))
    loss = surrogate + cfg.value_coef * value_loss - cfg.entropy_coef * ent
    stats = {
        "loss": loss,
        "surrogate": surrogate,
        "value_loss": value_loss,
        "entropy": ent,
        "approx_kl": jnp.mean(old_log_probs - new_log_probs),
        "clip_frac": jnp.mean((jnp.abs(ratio - 1.0) > cfg.clip).astype(jnp.float32)),
    }
    return loss, stats


def update_epoch(
    params: dict,
    opt_state: optim.adam.AdamState,
    cfg: PPOConfig,
    pcfg: policy_lib.PolicyConfig | None,
    traj: Trajectory,
    advantages: jax.Array,
    returns: jax.Array,
    *,
    policy: policy_lib.PolicyFns | None = None,
) -> tuple[dict, optim.adam.AdamState, dict]:
    """One full-batch gradient step over the flattened (T*B) experience.

    The paper trains full-batch for n_epochs (TF-Agents PPO default).  The
    (T*B) token axis is data-sharded; the psum of the gradient happens inside
    pjit via the sharded mean.
    """
    obs_f, act_f, lp_f, adv_f, ret_f = flatten_batch(
        traj, advantages, returns, normalize=cfg.normalize_advantages)

    (_, stats), grads = jax.value_and_grad(ppo_loss, has_aux=True)(
        params, cfg, pcfg, obs_f, act_f, lp_f, adv_f, ret_f, policy=policy
    )
    params, opt_state = optim.adam_update(cfg.adam, params, grads, opt_state)
    stats["grad_norm"] = optim.global_norm(grads)
    return params, opt_state, stats


def update(
    params: dict,
    opt_state: optim.adam.AdamState,
    cfg: PPOConfig,
    pcfg: policy_lib.PolicyConfig | None,
    traj: Trajectory,
    *,
    policy: policy_lib.PolicyFns | None = None,
) -> tuple[dict, optim.adam.AdamState, dict]:
    """Full PPO update: GAE once, then n_epochs gradient steps (lax.scan)."""
    advantages, returns = gae(traj, cfg.gamma, cfg.lam)

    def epoch(carry, _):
        params, opt_state = carry
        params, opt_state, stats = update_epoch(
            params, opt_state, cfg, pcfg, traj, advantages, returns,
            policy=policy
        )
        return (params, opt_state), stats

    (params, opt_state), stats_seq = jax.lax.scan(
        epoch, (params, opt_state), None, length=cfg.n_epochs
    )
    stats = jax.tree.map(lambda s: s[-1], stats_seq)
    stats["mean_return"] = jnp.mean(jnp.sum(traj.rewards, axis=0))
    return params, opt_state, stats
