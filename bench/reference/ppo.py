"""Plain float32 reference of the controller and its PPO training step.

The controller is the shared-trunk actor-critic of a multi-scenario fleet
with one scenario: per element, the flattened nodal observation goes
through a dense adapter to `d_embed`, `n_shared` dense ReLU layers, and a
dense head to one scalar.  The actor's mean is cs_max * sigmoid(head), with
a learned state-independent log-std; the critic averages the per-element
scalar.  The update is clipped PPO (Schulman et al. 2017) with GAE and
full-batch Adam epochs under a global-norm gradient clip, as in the paper
(gamma 0.995, lr 1e-4, 5 epochs, clip 0.2, no entropy bonus).

Nothing here imports the system under test.  `init_params` makes the
weights the benchmark hands to the program; the parameter tree has the
program's layout so it can be installed there.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

HALF_LOG_2PI = 0.5 * math.log(2.0 * math.pi)


class PPOSettings(NamedTuple):
    gamma: float = 0.995
    lam: float = 0.95
    clip: float = 0.2
    value_coef: float = 0.5
    n_epochs: int = 5
    lr: float = 1e-4
    grad_clip: float = 1.0
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8


def init_params(key, name: str, in_features: int, d_embed: int,
                n_shared: int, log_std: float = -1.6,
                mean_share: float = 0.5, head_scale: float = 1.0) -> dict:
    """Weights from the seed: w ~ N(0, 1/d_in), zero biases; the actor's
    output weights scaled by `head_scale` and its bias set so that the
    mean action starts near `mean_share` of cs_max."""
    def dense(k, d_in, d_out, scale=1.0, bias=0.0):
        return {"w": jax.random.normal(k, (d_in, d_out), jnp.float32)
                * (scale / math.sqrt(d_in)),
                "b": jnp.full((d_out,), bias, jnp.float32)}

    keys = iter(jax.random.split(key, 2 * n_shared + 4))
    shared = {part: [dense(next(keys), d_embed, d_embed)
                     for _ in range(n_shared)]
              for part in ("actor", "critic")}
    head = {"actor_in": dense(next(keys), in_features, d_embed),
            "critic_in": dense(next(keys), in_features, d_embed),
            "actor_out": dense(next(keys), d_embed, 1, head_scale,
                               math.log(mean_share / (1.0 - mean_share))),
            "critic_out": dense(next(keys), d_embed, 1),
            "log_std": jnp.full((), log_std, jnp.float32)}
    return {"shared": shared, "heads": {name: head}}


def _scalar(shared, adapter, out, feats):
    x = jax.nn.relu(feats @ adapter["w"] + adapter["b"])
    for layer in shared:
        x = jax.nn.relu(x @ layer["w"] + layer["b"])
    return (x @ out["w"] + out["b"])[..., 0]


def actor_mean(params, name, feats, cs_max):
    """feats (..., E, F) -> mean action (..., E) in [0, cs_max]."""
    h = params["heads"][name]
    return cs_max * jax.nn.sigmoid(_scalar(params["shared"]["actor"],
                                           h["actor_in"], h["actor_out"],
                                           feats))


def value(params, name, feats):
    h = params["heads"][name]
    return jnp.mean(_scalar(params["shared"]["critic"], h["critic_in"],
                            h["critic_out"], feats), axis=-1)


def log_prob(mean, std, action):
    z = (action - mean) / std
    return jnp.sum(-0.5 * z * z - jnp.log(std) - HALF_LOG_2PI, axis=-1)


def features(obs):
    """(..., E, n, n, n, 3) -> (..., E, F)."""
    return obs.reshape(obs.shape[:-4] + (-1,))


def gae(rewards, values, last_value, dones, gamma, lam):
    not_done = 1.0 - dones.astype(jnp.float32)
    nxt = jnp.concatenate([values[1:], last_value[None]], axis=0)
    deltas = rewards + gamma * nxt * not_done - values

    def back(carry, x):
        delta, nd = x
        adv = delta + gamma * lam * nd * carry
        return adv, adv

    _, adv = jax.lax.scan(back, jnp.zeros_like(deltas[-1]),
                          (deltas, not_done), reverse=True)
    return adv, adv + values


def ppo_loss(params, name, cs_max, s: PPOSettings, feats, actions, old_lp,
             adv, ret):
    mean = actor_mean(params, name, feats, cs_max)
    std = jnp.broadcast_to(jnp.exp(params["heads"][name]["log_std"]),
                           mean.shape)
    ratio = jnp.exp(log_prob(mean, std, actions) - old_lp)
    clipped = jnp.clip(ratio, 1.0 - s.clip, 1.0 + s.clip)
    surrogate = -jnp.mean(jnp.minimum(ratio * adv, clipped * adv))
    v = value(params, name, feats)
    return surrogate + s.value_coef * 0.5 * jnp.mean((v - ret) ** 2)


class Adam(NamedTuple):
    step: jax.Array
    m: dict
    v: dict


def adam_init(params) -> Adam:
    z = jax.tree.map(jnp.zeros_like, params)
    return Adam(jnp.zeros((), jnp.int32), z, jax.tree.map(jnp.copy, z))


def adam_step(s: PPOSettings, params, grads, st: Adam):
    leaves = jax.tree.leaves(grads)
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    scale = jnp.minimum(1.0, s.grad_clip / jnp.maximum(norm, 1e-12))
    grads = jax.tree.map(lambda g: g * scale, grads)
    t = st.step + 1
    b1c = 1.0 - s.b1 ** t.astype(jnp.float32)
    b2c = 1.0 - s.b2 ** t.astype(jnp.float32)
    m = jax.tree.map(lambda m, g: s.b1 * m + (1.0 - s.b1) * g, st.m, grads)
    v = jax.tree.map(lambda v, g: s.b2 * v + (1.0 - s.b2) * g * g, st.v,
                     grads)
    params = jax.tree.map(
        lambda p, m, v: p - s.lr * (m / b1c) / (jnp.sqrt(v / b2c) + s.eps),
        params, m, v)
    return params, Adam(t, m, v)


def update(params, opt: Adam, traj: dict, name: str, cs_max: float,
           s: PPOSettings = PPOSettings()):
    """One PPO iteration on a time-major trajectory dict (feats (T, B, E,
    F), actions (T, B, E), log_probs, rewards, dones, values (T, B),
    last_value (B,)).  Returns (params, opt, loss of the last epoch)."""
    adv, ret = gae(traj["rewards"], traj["values"], traj["last_value"],
                   traj["dones"], s.gamma, s.lam)
    flat = lambda x: x.reshape((-1,) + x.shape[2:])
    feats, actions, old_lp = (flat(traj["feats"]), flat(traj["actions"]),
                              flat(traj["log_probs"]))
    adv, ret = flat(adv), flat(ret)
    adv = (adv - jnp.mean(adv)) / (jnp.std(adv) + 1e-8)
    loss = None
    for _ in range(s.n_epochs):
        loss, grads = jax.value_and_grad(ppo_loss)(
            params, name, cs_max, s, feats, actions, old_lp, adv, ret)
        params, opt = adam_step(s, params, grads, opt)
    return params, opt, loss
