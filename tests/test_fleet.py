"""Fleet subsystem tests: broker ring semantics, scheduler determinism,
multitask heads, pipelined training, and the refactor's bit-identity pins.

The two acceptance-critical properties:

  * a mixed fleet (hit_les + channel_wm + burgers reduced) trains
    end-to-end through `FleetRunner.train` and replays BIT-IDENTICALLY
    through a checkpoint restore (the multi-scenario state tree — params,
    optimizer, broker rings — covers the in-flight trajectory);
  * the PolicyFns plumbing threaded through core/ leaves every
    single-scenario entry point bit-identical (rollout and PPO update
    through the adapter == the direct module functions).
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import envs, fleet
from repro.core import policy as policy_lib
from repro.core import ppo as ppo_lib
from repro.core import rollout as rollout_lib
from repro.fleet import broker, multitask, scheduler
from repro.fleet.pipeline import FleetRunner, FleetRunnerConfig

FLEET_NAMES = ("hit_les_reduced", "channel_wm_reduced", "burgers_reduced")


def _item(v: float) -> dict:
    return {"a": jnp.full((), v, jnp.float32),
            "b": jnp.full((2, 3), v, jnp.float32)}


def _runner(tmpdir, n_iterations=3, **cfg_kw) -> FleetRunner:
    kw = dict(n_iterations=n_iterations, eval_every=100, checkpoint_every=100,
              checkpoint_dir=str(tmpdir), async_checkpoint=False, bank_size=4)
    kw.update(cfg_kw)
    return fleet.make_fleet_runner(FLEET_NAMES, total_envs=6,
                                   run_cfg=FleetRunnerConfig(**kw),
                                   use_artifacts=False)


# --- broker ring buffers ------------------------------------------------------
def test_ring_wraparound():
    ring = broker.ring_init(_item(0.0), 3)
    assert broker.capacity(ring) == 3
    assert int(broker.size(ring)) == 0
    for v in range(1, 6):  # five pushes through a capacity-3 ring
        ring = broker.push(ring, _item(float(v)))
    assert int(ring.head) == 5
    assert int(broker.size(ring)) == 3
    # newest-first reads wrap correctly: 5, 4, 3 survive; 1, 2 evicted
    for age, want in ((0, 5.0), (1, 4.0), (2, 3.0)):
        got = broker.peek(ring, age)
        assert float(got["a"]) == want
        np.testing.assert_array_equal(np.asarray(got["b"]),
                                      np.full((2, 3), want, np.float32))


def test_ring_push_donated_matches_push():
    r1 = broker.ring_init(_item(0.0), 2)
    r2 = broker.ring_init(_item(0.0), 2)
    for v in (1.0, 2.0, 3.0):
        r1 = broker.push(r1, _item(v))
        r2 = broker.push_donated(r2, _item(v))
    assert int(r1.head) == int(r2.head)
    for a, b in zip(jax.tree.leaves(r1), jax.tree.leaves(r2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_broker_metrics_drain_ordered():
    b = broker.broker_init({}, metric_templates={"m": _item(0.0)["a"]},
                           metrics_capacity=4)
    for v in range(1, 7):
        b = broker.push_metrics(b, "m", jnp.float32(v))
    records = broker.drain_host(b)["m"]
    assert records == [3.0, 4.0, 5.0, 6.0]  # oldest-first, capacity-bounded


# --- scheduler ----------------------------------------------------------------
def _named_envs():
    return [(n, envs.make(n)) for n in FLEET_NAMES]


def test_schedule_cost_weighted_partition():
    costs = {"hit_les_reduced": 4.0, "channel_wm_reduced": 40.0,
             "burgers_reduced": 1.0}
    sched = scheduler.build_schedule(_named_envs(), 20, costs=costs,
                                     use_artifacts=False)
    by_name = {m.name: m for m in sched.members}
    assert sched.total_envs == 20
    # cheaper scenarios get more envs; everyone gets at least one
    assert (by_name["burgers_reduced"].n_envs
            > by_name["hit_les_reduced"].n_envs
            > by_name["channel_wm_reduced"].n_envs >= 1)
    assert abs(sum(m.weight for m in sched.members) - 1.0) < 1e-9
    # deterministic: same inputs, same partition
    again = scheduler.build_schedule(_named_envs(), 20, costs=costs,
                                     use_artifacts=False)
    assert [(m.name, m.n_envs) for m in again.members] == \
           [(m.name, m.n_envs) for m in sched.members]


def test_schedule_static_costs_from_configs():
    sched = scheduler.build_schedule(_named_envs(), 12, use_artifacts=False)
    by_name = {m.name: m for m in sched.members}
    assert sched.total_envs == 12
    # the 3-D channel step costs orders of magnitude more than 1-D Burgers
    assert by_name["channel_wm_reduced"].cost > by_name["burgers_reduced"].cost
    assert (by_name["burgers_reduced"].n_envs
            >= by_name["channel_wm_reduced"].n_envs)


def test_schedule_min_envs_guard():
    with pytest.raises(ValueError, match="total_envs"):
        scheduler.build_schedule(_named_envs(), 2, use_artifacts=False,
                                 costs={n: 1.0 for n in FLEET_NAMES})


def test_dryrun_cost_artifact_feeds_scheduler(tmp_path):
    """Measured fleet-cell costs reach the scheduler — matched by exact
    scenario, and only when EVERY member has one (measured XLA FLOPs and
    the static DOF proxy are different units; a partial set must not mix
    inside one partition)."""
    cell = {"status": "ok", "arch": "channel-wm",
            "variant": "channel_wm_reduced", "flops_per_env": 2.0e6}
    with open(tmp_path / "single_channel-wm_fleet_256.json", "w") as f:
        json.dump(cell, f)
    hit = {"status": "ok", "arch": "relexi-hit24", "flops_per_env": 1.0e6}
    with open(tmp_path / "single_relexi-hit24_fleet_256_elem16.json",
              "w") as f:
        json.dump(hit, f)

    assert scheduler.dryrun_step_cost(
        "channel_wm_reduced", artifact_dir=str(tmp_path)) == 2.0e6
    assert scheduler.dryrun_step_cost(
        "hit_les_24dof", artifact_dir=str(tmp_path)) == 1.0e6
    # a cell measured at another scale must not price this scenario
    assert scheduler.dryrun_step_cost(
        "channel_wm", artifact_dir=str(tmp_path)) is None
    assert scheduler.dryrun_step_cost(
        "burgers_reduced", artifact_dir=str(tmp_path)) is None

    # fully-measured fleet: the artifacts become the weights
    measured = [("channel_wm_reduced", envs.make("channel_wm_reduced")),
                ("hit_les_24dof", envs.make("hit_les_24dof"))]
    sched = scheduler.build_schedule(measured, 9,
                                     artifact_dir=str(tmp_path))
    assert sched.member("channel_wm_reduced").cost == 2.0e6
    assert sched.member("hit_les_24dof").cost == 1.0e6
    assert (sched.member("hit_les_24dof").n_envs
            > sched.member("channel_wm_reduced").n_envs)

    # partially-measured fleet (burgers has no cell): everyone falls back
    # to the static proxy rather than mixing units
    mixed = scheduler.build_schedule(_named_envs(), 9,
                                     artifact_dir=str(tmp_path))
    assert mixed.member("channel_wm_reduced").cost != 2.0e6


def test_scenario_keys_deterministic_and_distinct():
    seeds = [scheduler.scenario_seed(0, i) for i in range(4)]
    assert len(set(seeds)) == 4
    key = jax.random.PRNGKey(7)
    k_a = scheduler.rollout_key(key, 0, 3)
    k_b = scheduler.rollout_key(key, 1, 3)
    k_c = scheduler.rollout_key(key, 0, 4)
    assert not np.array_equal(np.asarray(k_a), np.asarray(k_b))
    assert not np.array_equal(np.asarray(k_a), np.asarray(k_c))
    # pure function of (seed, scenario, iteration): replay regenerates it
    np.testing.assert_array_equal(
        np.asarray(k_a), np.asarray(scheduler.rollout_key(key, 0, 3)))


# --- multitask heads ----------------------------------------------------------
def test_multitask_heads_respect_specs():
    named = _named_envs()
    mcfg = multitask.MultiTaskConfig.from_envs(named)
    params = multitask.init(jax.random.PRNGKey(0), mcfg)
    for name, env in named:
        bank = env.initial_state_bank(jax.random.PRNGKey(1), 2)
        obs = jnp.stack([env.reset_from_bank(bank, jnp.asarray(i))[1]
                         for i in range(2)])
        mean = multitask.actor_mean(params, mcfg, name, obs)
        assert mean.shape == (2,) + env.action_spec.shape
        assert bool(jnp.all(mean >= env.action_spec.low))
        assert bool(jnp.all(mean <= env.action_spec.high))
        assert multitask.value(params, mcfg, name, obs).shape == (2,)


def test_multitask_policy_drives_unchanged_rollout():
    """A scenario head plugs into core rollout via the PolicyFns bundle."""
    named = _named_envs()
    mcfg = multitask.MultiTaskConfig.from_envs(named)
    params = multitask.init(jax.random.PRNGKey(2), mcfg)
    name, env = named[2]  # burgers: cheapest
    u0 = env.initial_state_bank(jax.random.PRNGKey(3), 2)
    traj = jax.jit(lambda p, u, k: rollout_lib.rollout(
        p, None, env, u, k, policy=multitask.policy_fns(mcfg, name))
    )(params, u0, jax.random.PRNGKey(4))
    assert traj.obs.shape[:2] == (env.n_actions, 2)
    assert bool(jnp.all(jnp.isfinite(traj.rewards)))


def test_shared_trunk_is_actually_shared():
    """Gradients from one scenario's loss touch the shared trunk params."""
    named = _named_envs()
    mcfg = multitask.MultiTaskConfig.from_envs(named)
    params = multitask.init(jax.random.PRNGKey(5), mcfg)
    name, env = named[2]
    bank = env.initial_state_bank(jax.random.PRNGKey(6), 2)
    obs = env.reset_from_bank(bank, jnp.asarray(0))[1][None]

    grads = jax.grad(
        lambda p: jnp.sum(multitask.actor_mean(p, mcfg, name, obs)))(params)
    assert any(float(jnp.max(jnp.abs(g))) > 0.0
               for g in jax.tree.leaves(grads["shared"]["actor"]))


# --- refactor pin: default policy path is bit-identical -----------------------
def test_policy_fns_adapter_bit_identical():
    """The PolicyFns indirection added for the fleet must not perturb the
    single-scenario path: rollout and PPO update through an explicit
    default-policy bundle match the policy=None path bit-for-bit (which
    itself is pinned against the pre-refactor formulas by test_envs)."""
    env = envs.make("burgers_reduced")
    pcfg = policy_lib.PolicyConfig.from_specs(env.obs_spec, env.action_spec)
    params = policy_lib.init(jax.random.PRNGKey(0), pcfg)
    u0 = env.initial_state_bank(jax.random.PRNGKey(1), 2)
    key = jax.random.PRNGKey(2)

    roll = lambda policy: jax.jit(
        lambda p, u, k: rollout_lib.rollout(p, pcfg, env, u, k,
                                            policy=policy))(params, u0, key)
    t_default, t_adapter = roll(None), roll(policy_lib.policy_fns(pcfg))
    for got, want in zip(t_adapter, t_default):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    from repro import optim
    opt = optim.adam_init(params)
    cfg = ppo_lib.PPOConfig(n_epochs=2)
    upd = lambda policy: jax.jit(
        lambda p, o, t: ppo_lib.update(p, o, cfg, pcfg, t, policy=policy)
    )(params, opt, t_default)
    p_default, _, s_default = upd(None)
    p_adapter, _, s_adapter = upd(policy_lib.policy_fns(pcfg))
    for got, want in zip(jax.tree.leaves((p_adapter, s_adapter)),
                         jax.tree.leaves((p_default, s_default))):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --- end-to-end fleet training ------------------------------------------------
def test_mixed_fleet_trains_and_logs(tmp_path):
    runner = _runner(tmp_path / "fleet", n_iterations=3, eval_every=2)
    history = runner.train(resume=False)
    assert len(history) == 3
    for rec in history:
        assert rec["update_ok"] == 1.0
        for name in FLEET_NAMES:
            assert np.isfinite(rec[f"{name}/return_norm"])
            assert -1.0 <= rec[f"{name}/return_norm"] <= 1.0
    # the eval cadence fired and logged per-scenario held-out returns
    with open(runner.metrics_path) as f:
        logged = [json.loads(line) for line in f]
    assert any(f"{FLEET_NAMES[0]}/eval_return_norm" in r for r in logged)


def test_mixed_fleet_bit_replay_after_restore(tmp_path):
    """Same seed => same params, straight through a checkpoint restore of
    the multi-scenario state tree (params + optimizer + broker rings)."""
    def make(d):
        return _runner(d, n_iterations=3, checkpoint_every=2)

    a = make(tmp_path / "a")
    a.train(resume=False)
    b = make(tmp_path / "b")
    b.train(2, resume=False)     # stop mid-run at the checkpoint
    b2 = make(tmp_path / "b")    # fresh process-state, same directory
    assert b2.restore()
    assert b2.iteration == 2
    b2.train(3, resume=False)    # already restored; continue to the end
    for got, want in zip(jax.tree.leaves(b2.params), jax.tree.leaves(a.params)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_sync_mode_trains_with_timings(tmp_path):
    runner = _runner(tmp_path / "sync", n_iterations=2, pipelined=False)
    history = runner.train(resume=False)
    assert len(history) == 2
    for rec in history:
        assert rec["t_sample_s"] > 0.0 and rec["t_update_s"] > 0.0
        assert rec["update_ok"] == 1.0


def test_update_nonfinite_guard_keeps_params(tmp_path):
    """A poisoned trajectory must not advance params (in-graph guard —
    the pipelined loop never syncs to check on the host)."""
    runner = _runner(tmp_path / "guard", n_iterations=1)
    trajs = runner.forch.sample_all(runner.params, runner._keys(0))
    name = FLEET_NAMES[0]
    trajs[name] = trajs[name]._replace(
        rewards=trajs[name].rewards.at[0, 0].set(jnp.nan))
    new_params, _, stats = runner._update(
        runner.params, runner.opt_state, trajs, jnp.asarray(0, jnp.int32))
    assert float(stats["update_ok"]) == 0.0
    for got, want in zip(jax.tree.leaves(new_params),
                         jax.tree.leaves(runner.params)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --- PR-8 bugfix regressions ---------------------------------------------------
def test_scenario_seed_no_additive_collision():
    """The former additive stride `base_seed + 7919*(index+1)` made
    `(s, i+1)` and `(s+7919, i)` share a bank seed; the fold_in derivation
    must keep them distinct (and stay a pure, stable function)."""
    assert (scheduler.scenario_seed(0, 1)
            != scheduler.scenario_seed(7919, 0))
    assert (scheduler.scenario_seed(3, 2)
            != scheduler.scenario_seed(3 + 7919, 1))
    # pure + stable within a run lineage
    assert scheduler.scenario_seed(5, 2) == scheduler.scenario_seed(5, 2)
    seeds = {scheduler.scenario_seed(s, i)
             for s in range(4) for i in range(4)}
    assert len(seeds) == 16


def test_draw_initial_states_rejects_zero_envs(tmp_path):
    """`n_envs=0` used to fall through `n_envs or fleet.n_envs` and
    silently sample the FULL fleet."""
    runner = _runner(tmp_path / "zero_envs", n_iterations=1)
    orch = runner.forch.orchs[FLEET_NAMES[0]]
    key = jax.random.PRNGKey(0)
    with pytest.raises(ValueError, match="positive"):
        orch.draw_initial_states(key, n_envs=0)
    with pytest.raises(ValueError, match="positive"):
        orch.draw_initial_states(key, n_envs=-2)
    # None still means the configured fleet size; explicit counts hold
    assert orch.draw_initial_states(key).shape[0] == orch.fleet.n_envs
    assert orch.draw_initial_states(key, n_envs=2).shape[0] == 2


def test_dryrun_cost_zero_measurement_fails_loudly(tmp_path):
    """A record carrying a measured `flops_per_env=0.0` used to be
    silently discarded by a truthiness check; it must raise (a zero cost
    would hand the scenario an infinite env share).  A record WITHOUT the
    field keeps scanning to older artifacts."""
    broken = {"status": "ok", "variant": "burgers_reduced",
              "flops_per_env": 0.0}
    with open(tmp_path / "a_fleet_1.json", "w") as f:
        json.dump(broken, f)
    with pytest.raises(ValueError, match="non-positive"):
        scheduler.dryrun_step_cost("burgers_reduced",
                                   artifact_dir=str(tmp_path))
    # a record with NO `arch` field must not match a scenario through the
    # legacy-tag fallback (None == None used to price any unlisted
    # scenario off an unrelated cell)
    assert scheduler.dryrun_step_cost("hit_les_24dof",
                                      artifact_dir=str(tmp_path)) is None

    import os
    import time as time_mod
    old = {"status": "ok", "variant": "channel_wm_reduced",
           "flops_per_env": 5.0e5}
    with open(tmp_path / "old_fleet_1.json", "w") as f:
        json.dump(old, f)
    missing = {"status": "ok", "variant": "channel_wm_reduced"}
    with open(tmp_path / "new_fleet_1.json", "w") as f:
        json.dump(missing, f)
    now = time_mod.time()
    os.utime(tmp_path / "old_fleet_1.json", (now - 100, now - 100))
    os.utime(tmp_path / "new_fleet_1.json", (now, now))
    # the newest record has no measurement -> fall back to the older one
    assert scheduler.dryrun_step_cost(
        "channel_wm_reduced", artifact_dir=str(tmp_path)) == 5.0e5


def test_broker_drains_vector_metrics_json_ready(tmp_path):
    """A vector-valued metric leaf used to come back from `drain_host` as
    a numpy array and crash the runner's `float(v)` record conversion."""
    from repro.fleet.pipeline import _host_record

    template = {"loss": jnp.zeros(()),
                "per_scenario_return": jnp.zeros((3,))}
    b = broker.broker_init({}, metric_templates={"fleet": template},
                           metrics_capacity=4)
    item = {"loss": jnp.asarray(0.5),
            "per_scenario_return": jnp.asarray([1.0, 2.0, 3.0])}
    b = broker.push_metrics(b, "fleet", item)
    drained = broker.drain_host(b)["fleet"]
    assert len(drained) == 1
    rec = drained[0]
    assert isinstance(rec["loss"], float) and rec["loss"] == 0.5
    assert rec["per_scenario_return"] == [1.0, 2.0, 3.0]
    json.dumps(rec)  # JSON-serializable as drained
    host = _host_record(rec)
    assert host["per_scenario_return"] == [1.0, 2.0, 3.0]
    assert isinstance(host["loss"], float)


# --- scheduler _partition edge cases -------------------------------------------
def test_partition_min_envs_overshoot_shaved():
    """When the min_envs floor overshoots `total`, the largest members are
    shaved back (never below min_envs) until the budget holds."""
    # weights push everything to member 0; min_envs floors 1 and 2 up
    counts = scheduler._partition([100.0, 1.0, 1.0], 6, 2)
    assert sum(counts) == 6
    assert all(c >= 2 for c in counts)
    assert counts[0] == 2  # shaved from its raw share down to the budget


def test_partition_tie_break_by_position():
    """Equal weights with a non-divisible total: the remainder goes to the
    EARLIEST members (stable position tie-break, part of the determinism
    contract)."""
    assert scheduler._partition([1.0, 1.0, 1.0], 7, 1) == [3, 2, 2]
    assert scheduler._partition([1.0, 1.0, 1.0, 1.0], 6, 1) == [2, 2, 1, 1]
    # stable across calls
    assert (scheduler._partition([2.0, 1.0], 5, 1)
            == scheduler._partition([2.0, 1.0], 5, 1))


def test_partition_property_sums_and_respects_min():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=200, deadline=None)
    @given(
        weights=st.lists(st.floats(min_value=0.01, max_value=100.0,
                                   allow_nan=False, allow_infinity=False),
                         min_size=1, max_size=6),
        extra=st.integers(min_value=0, max_value=40),
        min_envs=st.integers(min_value=1, max_value=3))
    def prop(weights, extra, min_envs):
        total = min_envs * len(weights) + extra
        counts = scheduler._partition(weights, total, min_envs)
        assert sum(counts) == total
        assert all(c >= min_envs for c in counts)

    prop()


# --- single fleet program: conformance to per-scenario dispatch ----------------
def test_super_batch_rollout_bit_identical_to_dispatch(tmp_path):
    """The one-program super-batch rollout, sliced back to real env
    counts, reproduces `Orchestrator.sample_fleet` bit-for-bit per
    scenario at equal seeds (zero padding on a single-`data`-shard mesh;
    the scan bodies are structurally identical by construction)."""
    from repro.fleet import superbatch

    runner = _runner(tmp_path / "conform", n_iterations=1)
    prog = runner.program
    assert prog is not None
    keys = runner._keys(0)
    padded = jax.jit(prog.rollout_super_batch)(runner.params, keys)
    for m in runner.schedule.members:
        ref = runner.forch.orchs[m.name].sample_fleet(runner.params,
                                                      keys[m.name])
        got = superbatch.slice_traj(padded[m.name], m.n_envs)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_single_program_trains_bit_identical_to_dispatch(tmp_path):
    """Three iterations end-to-end: the single-program path and the
    per-scenario dispatch path produce bit-identical params (same seeds,
    same key schedule, same state tree)."""
    results = {}
    for flag in (True, False):
        runner = _runner(tmp_path / f"sp_{flag}", n_iterations=3,
                         single_program=flag)
        runner.train(resume=False)
        results[flag] = jax.device_get(runner.params)
    for got, want in zip(jax.tree.leaves(results[True]),
                         jax.tree.leaves(results[False])):
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("name", ["hit_les_reduced", "channel_wm_reduced"])
def test_guard_reverts_are_counted_per_scenario(tmp_path, name):
    """A planted bank row with negative density turns its env non-finite
    in every RL interval; the guard reverts it each step, and the fleet's
    metrics record counts those env-steps as `<scenario>/reverted_envs`."""
    runner = fleet.make_fleet_runner(
        (name,), total_envs=3,
        run_cfg=FleetRunnerConfig(n_iterations=1, eval_every=100,
                                  checkpoint_every=100,
                                  checkpoint_dir=str(tmp_path),
                                  async_checkpoint=False, bank_size=4),
        use_artifacts=False)
    orch = runner.forch.orchs[name]
    orch.bank = orch.bank.at[0, ..., 0].multiply(-1.0)
    # the prologue's rollout (trajectory 0, which update 0 consumes)
    u0, _ = runner.program.draw_padded_inputs(name, runner._keys(0)[name])
    planted = int(jnp.sum(jnp.any(u0[..., 0] < 0,
                                  axis=tuple(range(1, u0.ndim - 1)))))
    assert 0 < planted < 3
    (rec,) = runner.train(resume=False)
    assert rec[f"{name}/reverted_envs"] == planted * orch.env.n_actions
