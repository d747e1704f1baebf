"""The reference's side of a training cell: the program's input draws, the
rollout, and the pipelined schedule of the fleet's first iterations.

Iteration k of the pipelined fleet updates on trajectory k and rolls
trajectory k+1 with the parameters it started from, after a prologue that
rolls trajectory 0.  So rollout k uses params_{max(k-1, 0)}, and update k
turns params_k into params_{k+1} on trajectory k.

The inputs are drawn from the run's key exactly as the fleet's feed draws
them (bank rows and per-step action noise from
fold_in(fold_in(key, scenario), iteration)), so both sides see the same
initial states and noise.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ppo
from .hit_les import HITReference


def rollout_key(seed_key, iteration: int, scenario: int = 0):
    return jax.random.fold_in(jax.random.fold_in(seed_key, scenario),
                              iteration)


def draw_inputs(seed_key, iteration: int, n_envs: int, bank_size: int,
                n_actions: int, n_elements: int):
    """(bank row indices (B,), action noise (T, B, E)) of one rollout."""
    k_init, k_roll = jax.random.split(rollout_key(seed_key, iteration))
    idx = jax.random.randint(k_init, (n_envs,), 0, bank_size - 1)
    noise = jax.vmap(lambda kk: jax.random.normal(
        kk, (n_envs, n_elements)))(jax.random.split(k_roll, n_actions))
    return idx, noise


@functools.partial(jax.jit, static_argnums=(0, 2, 5))
def _rollout_block(ref: HITReference, params, name, u0, noise,
                   dtype=jnp.float32):
    cs_max = ref.cfg["cs_max"]
    u = ref.to_planar(u0)

    def body(carry, noise_t):
        u, t = carry
        feats = ppo.features(ref.observe(u))
        mean = ppo.actor_mean(params, name, feats, cs_max)
        std = jnp.broadcast_to(jnp.exp(params["heads"][name]["log_std"]),
                               mean.shape)
        action = mean + std * noise_t
        logp = ppo.log_prob(mean, std, action)
        val = ppo.value(params, name, feats)
        u, r = ref.step(u, action, dtype)
        done = jnp.full(r.shape, t + 1 >= ref.n_actions)
        return (u, t + 1), (feats, action, logp, r, done, val)

    (u, _), (feats, actions, logp, rewards, dones, values) = jax.lax.scan(
        body, (u, 0), noise)
    last = ppo.value(params, name, ppo.features(ref.observe(u)))
    return {"feats": feats, "actions": actions, "log_probs": logp,
            "rewards": rewards, "dones": dones, "values": values,
            "last_value": last}


def rollout(ref: HITReference, params, name: str, u0, noise, *,
            block: int, dtype=jnp.float32) -> dict:
    """Time-major trajectory of envs u0 (B, K, K, K, n, n, n, 5) under
    noise (T, B, E), computed `block` envs at a time."""
    parts = [_rollout_block(ref, params, name, u0[i:i + block],
                            noise[:, i:i + block], dtype)
             for i in range(0, u0.shape[0], block)]
    out = {}
    for key in parts[0]:
        axis = 0 if key == "last_value" else 1
        out[key] = jnp.concatenate([p[key] for p in parts], axis=axis)
    return out


_update = jax.jit(ppo.update, static_argnums=(3, 4, 5))


def follow(ref: HITReference, params0, name: str, bank, seed_key, *,
           n_envs: int, steps: int, block: int, dtype=jnp.float32,
           settings: ppo.PPOSettings = ppo.PPOSettings()) -> dict:
    """The first `steps` iterations of the pipelined fleet: per-iteration
    losses, the Adam state after the first iteration, the parameters after
    the last, and the trajectories (for planted faults)."""
    with jax.default_matmul_precision("highest"):
        trajs = []
        params = [params0]
        opts = [ppo.adam_init(params0)]
        losses = []
        for k in range(steps):
            while len(trajs) <= min(k + 1, steps - 1):
                j = len(trajs)
                idx, noise = draw_inputs(seed_key, j, n_envs,
                                         bank.shape[0], ref.n_actions, ref.E)
                trajs.append(rollout(ref, params[max(j - 1, 0)], name,
                                     jnp.take(bank, idx, axis=0), noise,
                                     block=block, dtype=dtype))
            p, o, loss = _update(
                params[k], opts[k], trajs[k], name, ref.cfg["cs_max"],
                settings)
            params.append(p)
            opts.append(o)
            losses.append(loss)
    return {"losses": [float(x) for x in losses], "opt_first": opts[1],
            "params": params, "trajs": trajs}
