"""The rest of a run, off the chip and at a test size: sound runs come out
correct, and each fault a cell can have, planted in the timed path, makes
`correct` false."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench import common
from bench.drivers import serve, train
from bench.reference import ppo
from conftest import CELLS, DATA, TRAIN_CELLS, tiny_traffic


def _train(harness, cell, seed=7):
    h = harness("hit_tiny.json", "train_tiny.json",
                common.load_json(common.limits_path(cell)), seed,
                seconds=1.0)
    h.traffic = tiny_traffic(cell)
    out = train.run(h)
    return out, all(c.ok for c in out.checks)


def _serve(harness, seed=7):
    lim = common.load_json(DATA / "serve_limits.json")
    out = serve.run(harness("hit_tiny.json", "serve_tiny.json", lim, seed,
                            seconds=1.0))
    return out, all(c.ok for c in out.checks)


@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_sound_training_run_is_correct(harness, cell):
    out, ok = _train(harness, cell)
    assert ok, [(c.name, c.value) for c in out.checks]
    assert out.attempted > 0 and out.failed == 0
    assert out.e2e["env_steps_per_s"] > 0


# Uniform C_s 0.05 turns one env of two non-finite within one RL interval
# at 32 DOF (the reference, bank seed 0); C_s 0 turns every env.
CS_BLOWS_UP = 0.05


@pytest.mark.parametrize("config", sorted({c["config"] for c in CELLS.values()
                                           if c["name"] in TRAIN_CELLS}))
def test_initial_policy_keeps_cs_where_the_solver_stays_finite(config):
    """Over the bank's states, the initial mean action less five of the
    policy's std stays above the C_s at which the solver blows up."""
    cfg = common.load_json(common.config_path(config))
    ref, bank, params, _ = train.make_inputs(cfg, common.seed_key(2**31 + 9))
    feats = ppo.features(ref.observe(ref.to_planar(bank)))
    mean = ppo.actor_mean(params, cfg["registry"], feats,
                          cfg["physics"]["cs_max"])
    std = float(jnp.exp(params["heads"][cfg["registry"]]["log_std"]))
    assert float(mean.min()) - 5 * std > CS_BLOWS_UP
    assert float(mean.max()) + 5 * std < cfg["physics"]["cs_max"]


def _unchanged(self, params, opt_state, broker, k, keys):
    return params, opt_state, broker


def _half_batch(update):
    def half(params, opt_state, ppo_cfg, mcfg, trajs, weights, k):
        trajs = {n: t._replace(**{f: (getattr(t, f)[: t.last_value.shape[0]
                                                    // 2]
                                      if f == "last_value" else
                                      getattr(t, f)[:, : t.last_value.shape[0]
                                                    // 2])
                                  for f in t._fields})
                 for n, t in trajs.items()}
        return update(params, opt_state, ppo_cfg, mcfg, trajs, weights, k)
    return half


def _reward_altered(slice_traj):
    def altered(traj, n_envs):
        t = slice_traj(traj, n_envs)
        return t._replace(rewards=t.rewards.at[0, 0].multiply(-1.0))
    return altered


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "reward_altered"])
@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_training_faults_are_not_correct(harness, monkeypatch, cell, fault):
    from repro.fleet import superbatch

    if fault == "state_unchanged":
        monkeypatch.setattr(superbatch.FleetProgram, "step", _unchanged)
    elif fault == "half_batch":
        monkeypatch.setattr(superbatch, "guarded_fleet_update",
                            _half_batch(superbatch.guarded_fleet_update))
    else:
        monkeypatch.setattr(superbatch, "slice_traj",
                            _reward_altered(superbatch.slice_traj))
    out, ok = _train(harness, cell, seed=11)
    assert not ok, [(c.name, c.value, c.limit) for c in out.checks]


def test_sound_serving_run_is_correct(harness):
    out, ok = _serve(harness)
    assert ok, [(c.name, c.value) for c in out.checks]
    assert out.failed == 0 and out.attempted == 200
    assert out.e2e["serve_p95_ms"] >= out.e2e["serve_p50_ms"] > 0


@pytest.mark.parametrize("fault", ["answer_altered", "half_batch"])
def test_serving_faults_are_not_correct(harness, monkeypatch, fault):
    from repro.serve import service

    if fault == "answer_altered":
        flush = service.ControllerService.flush

        def altered(self):
            out = flush(self)
            for uid, res in list(out.items())[:1]:
                act = np.array(res.action)
                act[0] = act.mean()
                out[uid] = service.ServeResult(uid, res.scenario, act,
                                               res.value)
            return out
        monkeypatch.setattr(service.ControllerService, "flush", altered)
    else:
        dispatch = service.ControllerService._dispatch

        def half(self, batch):
            obs = np.array(batch.obs)
            keep = (batch.n_valid + 1) // 2
            obs[keep:batch.n_valid] = obs[0]
            return dispatch(self, batch.__class__(
                batch.scenario, batch.uids, batch.slots, obs, batch.n_valid))
        monkeypatch.setattr(service.ControllerService, "_dispatch", half)
    out, ok = _serve(harness, seed=13)
    assert not ok, [(c.name, c.value, c.limit) for c in out.checks]
