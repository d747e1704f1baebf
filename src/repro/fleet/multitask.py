"""Multi-scenario policy: shared trunk, per-scenario adapters and heads.

One parameter tree serves every scenario in a heterogeneous fleet.  The
scenarios disagree on everything the single-scenario Conv policy hard-wires
— spatial rank (3-D HIT vs 1-D Burgers), per-element node count, channel
count, action bounds — so the sharing happens in a rank-free embedding
space instead:

    obs (..., E, *spatial, C)
      -> declared per-channel gains (ObsSpec.channel_specs — PR 4's
         declarations are what make this constructible without touching
         any solver)
      -> flatten per-element nodes to F = prod(spatial) * C features
      -> per-scenario ADAPTER: dense F -> d_embed            (scenario)
      -> shared TRUNK: n_shared_layers x [dense d -> d, ReLU] (shared)
      -> per-scenario HEAD: dense d -> 1                      (scenario)
    actor:  mean = low + (high - low) * sigmoid(head)  per element,
            per-scenario learnable log_std (TF-Agents continuous-PPO form,
            as in core/policy.py)
    critic: mean over elements of the per-element head scalar

Every per-scenario function is exposed as a `core.policy.PolicyFns` bundle
(`policy_fns(mcfg, name)`), so the UNCHANGED rollout scan and PPO loss in
`core/` drive it; `fleet_update` is the joint PPO step — one Adam update on
the whole tree from the cost-weighted sum of per-scenario losses, which is
what trains the shared trunk on all scenarios at once.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn, optim
from ..core import policy as policy_lib
from ..core import ppo as ppo_lib
from ..envs.base import Env


@dataclasses.dataclass(frozen=True)
class HeadSpec:
    """Static per-scenario head declaration, derived from the env specs."""

    name: str
    n_elements: int
    spatial: tuple[int, ...]
    channels: int
    gains: tuple[float, ...]
    act_low: float
    act_high: float

    @classmethod
    def from_env(cls, name: str, env: Env) -> "HeadSpec":
        obs, act = env.obs_spec, env.action_spec
        return cls(name=name, n_elements=obs.n_elements,
                   spatial=tuple(obs.spatial), channels=obs.channels,
                   gains=tuple(obs.channel_gains),
                   act_low=act.low, act_high=act.high)

    @property
    def in_features(self) -> int:
        """F: flattened per-element feature width."""
        return int(np.prod(self.spatial)) * self.channels


@dataclasses.dataclass(frozen=True)
class MultiTaskConfig:
    """Hashable static configuration (closed over by jit like PolicyConfig)."""

    heads: tuple[HeadSpec, ...]
    d_embed: int = 32
    n_shared_layers: int = 2
    log_std_init: float = -1.6

    def __post_init__(self):
        names = self.names
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate head names: {names}")

    @classmethod
    def from_envs(cls, named_envs, **kwargs) -> "MultiTaskConfig":
        """Build from [(name, env), ...] — each head from the env's specs."""
        return cls(heads=tuple(HeadSpec.from_env(n, e) for n, e in named_envs),
                   **kwargs)

    @property
    def names(self) -> tuple[str, ...]:
        return tuple(h.name for h in self.heads)

    def head(self, name: str) -> HeadSpec:
        for h in self.heads:
            if h.name == name:
                return h
        raise KeyError(f"unknown scenario head {name!r}; have {self.names}")


# --- parameters ---------------------------------------------------------------
def init(key: jax.Array, cfg: MultiTaskConfig) -> dict:
    k_shared, k_heads = jax.random.split(key)
    ka, kc = jax.random.split(k_shared)
    shared = {
        "actor": [nn.dense_init(k, cfg.d_embed, cfg.d_embed)
                  for k in jax.random.split(ka, cfg.n_shared_layers)],
        "critic": [nn.dense_init(k, cfg.d_embed, cfg.d_embed)
                   for k in jax.random.split(kc, cfg.n_shared_layers)],
    }
    heads = {}
    for h, kh in zip(cfg.heads, jax.random.split(k_heads, len(cfg.heads))):
        k1, k2, k3, k4 = jax.random.split(kh, 4)
        heads[h.name] = {
            "actor_in": nn.dense_init(k1, h.in_features, cfg.d_embed),
            "critic_in": nn.dense_init(k2, h.in_features, cfg.d_embed),
            "actor_out": nn.dense_init(k3, cfg.d_embed, 1),
            "critic_out": nn.dense_init(k4, cfg.d_embed, 1),
            "log_std": jnp.full((), cfg.log_std_init, jnp.float32),
        }
    return {"shared": shared, "heads": heads}


def param_count(params: dict) -> int:
    return nn.param_count(params)


# --- forward ------------------------------------------------------------------
def _features(head: HeadSpec, obs: jax.Array) -> jax.Array:
    """(..., E, *spatial, C) -> (..., E, F) with declared gains applied."""
    x = obs
    if any(g != 1.0 for g in head.gains):
        x = x * jnp.asarray(head.gains, x.dtype)
    lead = x.shape[: x.ndim - (len(head.spatial) + 1)]
    return x.reshape(lead + (head.in_features,))


def _head_scalar(shared: list, adapter: dict, out: dict,
                 head: HeadSpec, obs: jax.Array) -> jax.Array:
    """Adapter -> shared trunk -> head: per-element scalar (..., E)."""
    x = jax.nn.relu(nn.dense(adapter, _features(head, obs)))
    for layer in shared:
        x = jax.nn.relu(nn.dense(layer, x))
    return nn.dense(out, x)[..., 0]


def actor_mean(params: dict, cfg: MultiTaskConfig, name: str,
               obs: jax.Array) -> jax.Array:
    h = cfg.head(name)
    p = params["heads"][name]
    logits = _head_scalar(params["shared"]["actor"], p["actor_in"],
                          p["actor_out"], h, obs)
    return h.act_low + (h.act_high - h.act_low) * jax.nn.sigmoid(logits)


def value(params: dict, cfg: MultiTaskConfig, name: str,
          obs: jax.Array) -> jax.Array:
    h = cfg.head(name)
    p = params["heads"][name]
    per_elem = _head_scalar(params["shared"]["critic"], p["critic_in"],
                            p["critic_out"], h, obs)
    return jnp.mean(per_elem, axis=-1)


def distribution(params: dict, cfg: MultiTaskConfig, name: str,
                 obs: jax.Array) -> tuple[jax.Array, jax.Array]:
    mean = actor_mean(params, cfg, name, obs)
    std = jnp.exp(params["heads"][name]["log_std"]).astype(mean.dtype)
    return mean, jnp.broadcast_to(std, mean.shape)


def sample_action(key: jax.Array, params: dict, cfg: MultiTaskConfig,
                  name: str, obs: jax.Array) -> tuple[jax.Array, jax.Array]:
    mean, std = distribution(params, cfg, name, obs)
    noise = jax.random.normal(key, mean.shape, mean.dtype)
    action = mean + std * noise
    return action, policy_lib.log_prob(mean, std, action)


# --- PolicyFns bundle (what core/rollout + core/ppo consume) ------------------
def policy_fns(cfg: MultiTaskConfig, name: str) -> policy_lib.PolicyFns:
    """The scenario-`name` head as the standard policy callable bundle."""
    cfg.head(name)  # fail fast on unknown scenarios
    return policy_lib.PolicyFns(
        sample=partial(_sample_h, cfg, name),
        mean=partial(_mean_h, cfg, name),
        dist=partial(_dist_h, cfg, name),
        value=partial(_value_h, cfg, name),
    )


def _sample_h(cfg, name, key, params, obs):
    return sample_action(key, params, cfg, name, obs)


def _mean_h(cfg, name, params, obs):
    return actor_mean(params, cfg, name, obs)


def _dist_h(cfg, name, params, obs):
    return distribution(params, cfg, name, obs)


def _value_h(cfg, name, params, obs):
    return value(params, cfg, name, obs)


# --- joint PPO update ---------------------------------------------------------
def fleet_update(
    params: dict,
    opt_state,
    cfg: ppo_lib.PPOConfig,
    mcfg: MultiTaskConfig,
    trajs: dict[str, ppo_lib.Trajectory],
    weights: dict[str, float],
) -> tuple[dict, object, dict]:
    """One joint PPO update over every scenario's trajectory batch.

    GAE + flattening + advantage normalization run PER SCENARIO (each
    scenario's reward scale normalizes against itself), the clipped losses
    combine as  sum_s w_s * L_s  with w_s the scheduler's env-share weights
    (so the joint loss is an unweighted per-environment mean across the
    fleet), and `n_epochs` full-batch Adam steps train adapters, heads, and
    the shared trunk together.  Iteration order over scenarios is the
    declared head order — part of the determinism contract.
    """
    names = [n for n in mcfg.names if n in trajs]
    flat: dict[str, tuple] = {}
    for name in names:
        traj = trajs[name]
        adv, ret = ppo_lib.gae(traj, cfg.gamma, cfg.lam)
        flat[name] = ppo_lib.flatten_batch(
            traj, adv, ret, normalize=cfg.normalize_advantages)

    def loss_fn(params):
        total = 0.0
        stats: dict[str, jax.Array] = {}
        for name in names:
            loss_s, st = ppo_lib.ppo_loss(
                params, cfg, None, *flat[name],
                policy=policy_fns(mcfg, name))
            total = total + weights[name] * loss_s
            for k, v in st.items():
                stats[f"{name}/{k}"] = v
        stats["loss"] = total
        return total, stats

    def epoch(carry, _):
        params, opt_state = carry
        (_, stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        params, opt_state = optim.adam_update(cfg.adam, params, grads,
                                              opt_state)
        stats["grad_norm"] = optim.global_norm(grads)
        return (params, opt_state), stats

    (params, opt_state), stats_seq = jax.lax.scan(
        epoch, (params, opt_state), None, length=cfg.n_epochs)
    stats = jax.tree.map(lambda s: s[-1], stats_seq)
    for name in names:
        stats[f"{name}/mean_return"] = jnp.mean(
            jnp.sum(trajs[name].rewards, axis=0))
        if trajs[name].reverted is not None:
            # env-steps whose advance the blow-up guard reverted
            stats[f"{name}/reverted_envs"] = jnp.sum(
                trajs[name].reverted.astype(jnp.float32))
    return params, opt_state, stats
