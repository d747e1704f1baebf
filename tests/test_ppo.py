"""PPO / policy tests: GAE closed forms, clip invariants, Table-2 policy."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.core import policy as policy_lib, ppo
from repro.launch import mesh as mesh_lib


def _traj(rewards, values, last_value, dones=None):
    t, b = rewards.shape
    dones = jnp.zeros((t, b), bool).at[-1].set(True) if dones is None else dones
    return ppo.Trajectory(
        obs=jnp.zeros((t, b, 1, 2, 2, 2, 3)),
        actions=jnp.zeros((t, b, 1)),
        log_probs=jnp.zeros((t, b)),
        rewards=rewards,
        dones=dones,
        values=values,
        last_value=last_value,
    )


def test_gae_closed_form_three_steps():
    gamma, lam = 0.9, 0.8
    r = jnp.asarray([[1.0], [2.0], [3.0]])
    v = jnp.asarray([[0.5], [0.6], [0.7]])
    traj = _traj(r, v, jnp.asarray([9.9]))  # terminal: last_value unused
    adv, ret = ppo.gae(traj, gamma, lam)
    d2 = 3.0 - 0.7                       # terminal step
    d1 = 2.0 + gamma * 0.7 - 0.6
    d0 = 1.0 + gamma * 0.6 - 0.5
    a2 = d2
    a1 = d1 + gamma * lam * a2
    a0 = d0 + gamma * lam * a1
    np.testing.assert_allclose(np.asarray(adv[:, 0]), [a0, a1, a2], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(ret), np.asarray(adv + v), rtol=1e-6)


def test_gae_bootstrap_on_truncation():
    gamma, lam = 0.99, 0.95
    r = jnp.asarray([[1.0]])
    v = jnp.asarray([[2.0]])
    traj = _traj(r, v, jnp.asarray([3.0]), dones=jnp.zeros((1, 1), bool))
    adv, _ = ppo.gae(traj, gamma, lam)
    np.testing.assert_allclose(float(adv[0, 0]), 1.0 + gamma * 3.0 - 2.0,
                               rtol=1e-6)


def test_policy_param_count_matches_table2():
    """Paper Table 2: ~3,300 parameters for the N=5 (n=6) policy."""
    cfg = policy_lib.PolicyConfig(n_nodes=6)
    params = policy_lib.init(jax.random.PRNGKey(0), cfg)
    assert policy_lib.param_count(params) == 3294  # 3,293 conv + log_std


def test_policy_output_dims_table2():
    """Layer plan for n=6 must match Table 2 exactly."""
    assert policy_lib._conv_plan(6) == [
        (3, 8, "SAME"), (3, 8, "VALID"), (3, 4, "VALID"), (2, 1, "VALID")]


def test_policy_action_range():
    cfg = policy_lib.PolicyConfig(n_nodes=4, cs_max=0.5)
    params = policy_lib.init(jax.random.PRNGKey(1), cfg)
    obs = jax.random.normal(jax.random.PRNGKey(2), (3, 8, 4, 4, 4, 3))
    mean = policy_lib.actor_mean(params, cfg, obs)
    assert mean.shape == (3, 8)
    assert bool(jnp.all(mean >= 0.0)) and bool(jnp.all(mean <= 0.5))


def test_log_prob_matches_gaussian():
    mean = jnp.asarray([[0.1, 0.2]])
    std = jnp.asarray([[0.3, 0.3]])
    a = jnp.asarray([[0.0, 0.5]])
    lp = policy_lib.log_prob(mean, std, a)
    want = sum(
        -0.5 * ((ai - mi) / s) ** 2 - np.log(s) - 0.5 * np.log(2 * np.pi)
        for ai, mi, s in [(0.0, 0.1, 0.3), (0.5, 0.2, 0.3)])
    np.testing.assert_allclose(float(lp[0]), want, rtol=1e-5)


def test_ppo_clip_kills_gradient_outside_trust_region():
    """If the ratio is already far outside the clip range and the advantage
    pushes it further out, the surrogate gradient must vanish."""
    cfg = ppo.PPOConfig(clip=0.2)
    adv = jnp.asarray([1.0])  # positive advantage

    def surrogate(delta_logp):
        ratio = jnp.exp(delta_logp)
        clipped = jnp.clip(ratio, 0.8, 1.2)
        return -jnp.mean(jnp.minimum(ratio * adv, clipped * adv))

    g_inside = jax.grad(surrogate)(jnp.asarray(0.0))
    g_outside = jax.grad(surrogate)(jnp.asarray(1.0))  # ratio e >> 1.2
    assert abs(float(g_outside)) < 1e-8
    assert abs(float(g_inside)) > 1e-3


def test_update_improves_surrogate_on_fixed_batch():
    """Five epochs of PPO on one trajectory should increase the likelihood of
    positive-advantage actions (loss decreases)."""
    pcfg = policy_lib.PolicyConfig(n_nodes=4)
    params = policy_lib.init(jax.random.PRNGKey(3), pcfg)
    t, b, e = 4, 3, 8
    key = jax.random.PRNGKey(4)
    obs = jax.random.normal(key, (t, b, e, 4, 4, 4, 3))
    mean, std = policy_lib.distribution(params, pcfg, obs)
    actions = mean + 0.1
    logp = policy_lib.log_prob(mean, std, actions)
    traj = ppo.Trajectory(
        obs=obs, actions=actions, log_probs=logp,
        rewards=jnp.ones((t, b)),
        dones=jnp.zeros((t, b), bool).at[-1].set(True),
        values=policy_lib.value(params, pcfg, obs),
        last_value=jnp.zeros((b,)),
    )
    cfg = ppo.PPOConfig()
    opt = optim.adam_init(params)
    adv, ret = ppo.gae(traj, cfg.gamma, cfg.lam)
    flat = jax.tree.map(lambda x: x.reshape((-1,) + x.shape[2:]),
                        (traj.obs, traj.actions, traj.log_probs, adv, ret))
    l0 = ppo.ppo_loss(params, cfg, pcfg, *flat)[0]
    new_params, _, stats = ppo.update(params, opt, cfg, pcfg, traj)
    l1 = ppo.ppo_loss(new_params, cfg, pcfg, *flat)[0]
    assert float(l1) < float(l0)
    assert np.isfinite(float(stats["loss"]))


def test_adam_matches_reference_first_step():
    cfg = optim.AdamConfig(lr=0.1, b1=0.9, b2=0.999, eps=1e-8)
    params = {"w": jnp.asarray([1.0, -2.0])}
    grads = {"w": jnp.asarray([0.5, 0.5])}
    state = optim.adam_init(params)
    new, state = optim.adam_update(cfg, params, grads, state)
    # first step: mhat = g, vhat = g^2 -> delta = g/(|g|+eps) = sign(g)
    np.testing.assert_allclose(np.asarray(new["w"]), [0.9, -2.1], rtol=1e-5)


def test_compressed_psum_int8_error_feedback():
    """int8 psum with error feedback: the residual carries the quantization
    error so the running sum stays unbiased."""
    from repro.core import compression
    mesh = mesh_lib.auto_mesh((1,), ("pod",))
    from jax.sharding import PartitionSpec as P

    g = {"w": jnp.linspace(-1.0, 1.0, 16)}

    def f(x):
        red, err = compression.compressed_psum(x, "pod", method="int8")
        return red, err

    red, err = jax.shard_map(f, mesh=mesh, in_specs=({"w": P()},),
                         out_specs=({"w": P()}, {"w": P()}))(g)
    np.testing.assert_allclose(np.asarray(red["w"] + err["w"]),
                               np.asarray(g["w"]), atol=1e-6)
