"""The wall-modelled channel cell (`wmles2000_train_fleet16`) off the chip:
it resolves from new entries and files alone; at a test size its driver's
sound runs come out correct, every planted fault and the bfloat16 control
fail a limit, and the reference's rollout is the program's; its
configuration's wall-model budget and initial policy are sound."""
import dataclasses
import math
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import common
from bench.counts import channel as counts
from bench.counts import hit as hit_counts
from bench.drivers import train_channel
from bench.reference import ppo
from bench.reference import training as ref_training
from bench.reference.channel_les import ChannelReference, reichardt_uplus
from conftest import DATA
from test_drivers import _half_batch, _reward_altered, _unchanged

CELL = "wmles2000_train_fleet16"
MANIFEST = common.load_json(common.ROOT / "BENCHMARK.json")
CONFIG = common.load_json(common.config_path("channel_wmles_re2000"))
TINY_TRAFFIC = {"driver": "train_channel", "n_envs": 4,
                "steps_compared": 2, "reference_block": 2}


def _limits():
    return common.load_json(common.limits_path(CELL))


def _run(harness, seed=7):
    h = harness("channel_tiny.json", "train_tiny.json", _limits(), seed,
                seconds=1.0)
    h.traffic = TINY_TRAFFIC
    out = train_channel.run(h)
    return out, all(c.ok for c in out.checks)


def test_cell_resolves_from_new_entries():
    common.validate(MANIFEST)
    e2e, per_layer = common.cell_metrics(MANIFEST, CELL)
    assert {m["name"] for m in e2e} == {"env_steps_per_s", "setup_s"}
    assert {m["name"] for m in per_layer} == {
        "wall_rhs_kernel_time_share", "fused_wall_rhs_roofline",
        "device_idle_share.wall", "unscoped_device_share.wall",
        "guard_revert_share.wall"}
    cell = {w["name"]: w for w in MANIFEST["workloads"]}[CELL]
    traffic = common.load_json(common.traffic_path(cell["traffic"]))
    steps = traffic["steps_compared"]
    assert set(_limits()) == {f"loss_gap_{k}" for k in range(steps)} | {
        "grad_gap", "step_gap"}
    for m in per_layer:
        assert common.load_reader(m["name"])({"trace": None}) is None


def test_sound_run_is_correct(harness):
    out, ok = _run(harness)
    assert ok, [(c.name, c.value) for c in out.checks]
    assert out.attempted > 0 and out.failed == 0
    assert out.e2e["env_steps_per_s"] > 0
    assert out.context["rhs_kernel"] == "fused_wall_rhs"
    assert out.context["reverted_env_steps"] == 0.0
    assert common.load_reader("guard_revert_share.wall")(out.context) == 0.0


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "reward_altered"])
def test_faults_are_not_correct(harness, monkeypatch, fault):
    from repro.fleet import superbatch

    if fault == "state_unchanged":
        monkeypatch.setattr(superbatch.FleetProgram, "step", _unchanged)
    elif fault == "half_batch":
        monkeypatch.setattr(superbatch, "guarded_fleet_update",
                            _half_batch(superbatch.guarded_fleet_update))
    else:
        monkeypatch.setattr(superbatch, "slice_traj",
                            _reward_altered(superbatch.slice_traj))
    out, ok = _run(harness, seed=11)
    assert not ok, [(c.name, c.value, c.limit) for c in out.checks]


def test_control_and_faults_fail_a_limit():
    config = common.load_json(DATA / "channel_tiny.json")
    out = train_channel.readings(config, TINY_TRAFFIC, 5,
                                 tempfile.mkdtemp())
    limits = _limits()
    for label in ("control_bf16", "state_unchanged", "half_batch",
                  "reward_altered"):
        assert [k for k, v in out[label].items() if not v <= limits[k]], (
            label, out[label])


def test_reference_rollout_is_the_programs():
    """The prologue's trajectory of the program (natural-layout channel on
    the CPU) against the reference's rollout from the same weights, bank
    rows and noise: the same observations, actions and rewards to float32
    rounding over three RL steps (rewards within 1e-4, measured ~1e-6)."""
    from repro.fleet import broker as broker_lib

    config = common.load_json(DATA / "channel_tiny.json")
    ref, bank, params, run_key, runner = train_channel.build(
        config, 3, jax.random.PRNGKey(3), tempfile.mkdtemp())
    name = config["registry"]
    runner.broker = runner.program.prologue(runner.params, runner.broker,
                                            runner._keys(0))
    got = broker_lib.latest_traj(runner.broker, name)
    idx, noise = ref_training.draw_inputs(run_key, 0, 3, bank.shape[0],
                                          ref.n_actions, ref.E)
    with jax.default_matmul_precision("highest"):
        want = ref_training.rollout(ref, params, name,
                                    jnp.take(bank, idx, axis=0), noise,
                                    block=3)
    np.testing.assert_allclose(np.asarray(got.obs).reshape(
        want["feats"].shape), np.asarray(want["feats"]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(got.actions),
                               np.asarray(want["actions"]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(got.rewards),
                               np.asarray(want["rewards"]), atol=1e-4)
    assert not np.any(np.asarray(got.reverted))


def test_wm_iters_converge_at_the_matching_point():
    """The configuration's Reichardt rounds invert the wall law at the
    matching height (y+ 250) to 1e-6 relative over the bank's bulk range
    (0.75 to 1.25 of the reference velocity there)."""
    p = CONFIG["physics"]
    nu, kappa = p["nu"], p["kappa"]
    y_m = 0.5 * p["lengths"][1] / p["n_elem"][1]
    assert y_m * p["u_tau"] / nu == pytest.approx(250.0)

    def invert(up, rounds):
        ut = math.sqrt(nu * up / y_m + 1e-12)
        for _ in range(rounds):
            u_plus = max(float(reichardt_uplus(y_m * ut / nu, kappa,
                                               xp=np)), 1e-6)
            ut = math.sqrt(ut * up / u_plus + 1e-14)
        return ut

    assert invert(p["u_tau"] * float(reichardt_uplus(
        250.0, kappa, xp=np)), 200) == pytest.approx(p["u_tau"], rel=1e-9)
    for bulk in (0.75, 1.0, 1.25):
        up = bulk * p["u_tau"] * float(reichardt_uplus(250.0, kappa, xp=np))
        exact = invert(up, 200)
        assert abs(invert(up, p["wm_iters"]) - exact) / exact < 1e-6, bulk


def test_reference_bulk_is_the_bulk_velocity():
    """u_tau and nu give Reichardt's profile a bulk velocity of u_b."""
    ref = ChannelReference(CONFIG["physics"])
    w2 = ref.w2
    bulk = float(np.sum(ref.u_ref * w2[None, :]) / ref.Ky)
    assert bulk == pytest.approx(CONFIG["physics"]["u_bulk"], rel=2e-2)


def test_initial_policy_keeps_the_equilibrium_wall_model():
    """Over the bank's states, every wall element's initial mean action
    lies within 0.1 of 1 (the equilibrium wall model), and five of the
    policy's std keep it inside (0.5, 1.5)."""
    ref, bank, params, _ = train_channel.make_inputs(
        CONFIG, common.seed_key(2**31 + 9))
    feats = ppo.features(ref.observe(ref.to_planar(bank)))
    mean = ppo.actor_mean(params, CONFIG["registry"], feats,
                          CONFIG["physics"]["a_max"])
    std = float(jnp.exp(params["heads"][CONFIG["registry"]]["log_std"]))
    assert float(jnp.max(jnp.abs(mean - 1.0))) < 0.1
    assert float(mean.min()) - 5 * std > 0.5
    assert float(mean.max()) + 5 * std < 1.5


def test_counts_at_the_configuration():
    """The wall RHS count is the HIT count less Lundgren, plus the
    pressure gradient and the wall columns, less one y face per column;
    bytes are read off the call's shapes."""
    p = CONFIG["physics"]
    n, (kx, ky, kz) = p["n_poly"] + 1, p["n_elem"]
    flops, nbytes = counts.rhs_call(n, (kx, ky, kz), p["wm_iters"], 16)
    nodes = 16 * kx * ky * kz * n**3
    cols = 16 * kx * kz * n * n
    per_col = 6 * n + 18 + 20 * p["wm_iters"]
    want = ((hit_counts.rhs_flops_per_node(n) - 25) * nodes - 45 * cols
            + 2 * cols * per_col)
    assert flops == pytest.approx(want)
    assert nbytes == nodes * 40 + 16 * kx * ky * kz * 4
    shape = (16, kx, ky, kz, n, n, n, 5)
    assert counts.rhs_call_from_state(shape, p["wm_iters"]) == (flops,
                                                                 nbytes)


def test_a_program_without_the_wall_kernel_is_refused(harness, monkeypatch):
    """The parent program, without `fused_wall_rhs`, fails at once."""
    from repro.kernels import rhs

    monkeypatch.delattr(rhs, "fused_wall_rhs_planar")
    with pytest.raises(RuntimeError, match="planar wall RHS"):
        _run(harness)


def test_physics_matches_the_program_config():
    """Every physics key is a field of the program's ChannelConfig."""
    from repro.cfd.channel import ChannelConfig

    fields = {f.name for f in dataclasses.fields(ChannelConfig)}
    assert set(CONFIG["physics"]) <= fields
