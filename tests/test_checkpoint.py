"""Fault tolerance: atomic checkpoints, corruption detection, crash-replay
recovery, elastic mesh-shape changes."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import relexi_hit
from repro.core import checkpoints
from repro.core.orchestrator import FleetConfig
from repro.core.runner import Runner, RunnerConfig
from repro.launch import mesh as mesh_lib


def _tree():
    return {"a": jnp.arange(6.0).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.int32)}}


def test_roundtrip(tmp_path):
    d = str(tmp_path / "ck")
    tree = _tree()
    checkpoints.save(d, 3, tree, meta={"note": "x"})
    got, manifest = checkpoints.restore(d, 3, tree)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert manifest["meta"]["note"] == "x"
    assert checkpoints.latest_step(d) == 3


def test_corruption_detected(tmp_path):
    d = str(tmp_path / "ck")
    checkpoints.save(d, 1, _tree())
    path = os.path.join(d, "step_00000001", "0.npy")
    raw = bytearray(open(path, "rb").read())
    raw[-1] ^= 0xFF
    open(path, "wb").write(bytes(raw))
    with pytest.raises(checkpoints.IntegrityError):
        checkpoints.restore(d, 1, _tree())


def test_incomplete_checkpoint_skipped(tmp_path):
    d = str(tmp_path / "ck")
    checkpoints.save(d, 1, _tree())
    # simulate a crash mid-write: step dir without manifest
    os.makedirs(os.path.join(d, "step_00000005"))
    assert checkpoints.latest_step(d) == 1


def test_pruning_keeps_newest(tmp_path):
    d = str(tmp_path / "ck")
    for s in range(5):
        checkpoints.save(d, s, _tree(), keep=2)
    assert checkpoints.all_steps(d) == [3, 4]


def test_restore_with_shardings(tmp_path):
    from jax.sharding import NamedSharding, PartitionSpec as P
    d = str(tmp_path / "ck")
    tree = _tree()
    checkpoints.save(d, 0, tree)
    mesh = mesh_lib.auto_mesh((1,), ("data",))
    sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)
    got, _ = checkpoints.restore(d, 0, tree, shardings=sh)
    assert got["a"].sharding == NamedSharding(mesh, P())


def test_runner_recovers_from_injected_failure(tmp_path):
    """Paper-scale fleets lose nodes; the runner must replay the iteration
    deterministically from consistent state."""
    env_cfg = relexi_hit.reduced()
    boom = {"done": False}

    def injector(k):
        if k == 1 and not boom["done"]:
            boom["done"] = True
            raise RuntimeError("injected node failure")

    r = Runner(env_cfg, FleetConfig(n_envs=2, bank_size=3),
               run_cfg=RunnerConfig(n_iterations=2, eval_every=100,
                                    checkpoint_every=1,
                                    checkpoint_dir=str(tmp_path / "rl"),
                                    async_checkpoint=False),
               failure_injector=injector)
    history = r.train()
    assert len(history) == 2
    assert boom["done"]
    # metrics file records the retry
    lines = [json.loads(l) for l in open(r.metrics_path)]
    assert any("retry" in rec for rec in lines)


def test_runner_resume_deterministic(tmp_path):
    """Same seed + checkpoint resume == uninterrupted run (bitwise params)."""
    env_cfg = relexi_hit.reduced()
    ck1 = str(tmp_path / "a")
    r1 = Runner(env_cfg, FleetConfig(n_envs=2, bank_size=3),
                run_cfg=RunnerConfig(n_iterations=2, eval_every=100,
                                     checkpoint_every=1, checkpoint_dir=ck1,
                                     async_checkpoint=False))
    r1.train()
    # interrupted run: 1 iteration, then a fresh Runner resumes to 2
    ck2 = str(tmp_path / "b")
    r2a = Runner(env_cfg, FleetConfig(n_envs=2, bank_size=3),
                 run_cfg=RunnerConfig(n_iterations=1, eval_every=100,
                                      checkpoint_every=1, checkpoint_dir=ck2,
                                      async_checkpoint=False))
    r2a.train()
    r2b = Runner(env_cfg, FleetConfig(n_envs=2, bank_size=3),
                 run_cfg=RunnerConfig(n_iterations=2, eval_every=100,
                                      checkpoint_every=1, checkpoint_dir=ck2,
                                      async_checkpoint=False))
    r2b.train()
    for a, b in zip(jax.tree.leaves(r1.params), jax.tree.leaves(r2b.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


def test_elastic_fleet_resize():
    from repro.core import elastic
    mesh = mesh_lib.auto_mesh((1,), ("data",))
    assert elastic.elastic_fleet(16, mesh) == 16
    assert elastic.elastic_fleet(16, None) == 16


def test_lm_train_checkpoint_resume(tmp_path):
    """launch/train.py-style resume: params + stream cursor restored."""
    from repro import configs, optim
    from repro.data import TokenStream
    from repro.models import api
    cfg = configs.get_reduced("h2o-danube-1.8b")
    params = api.init(jax.random.PRNGKey(0), cfg)
    opt = optim.adam_init(params)
    stream = TokenStream(cfg, 2, 16, seed=7)
    step = jax.jit(lambda p, o, b: api.train_step(p, o, b, cfg))
    params, opt, _ = step(params, opt, stream.next())
    d = str(tmp_path / "lm")
    checkpoints.save(d, 1, {"params": jax.device_get(params),
                            "opt": jax.device_get(opt)},
                     meta={"stream": stream.state_dict()})
    tree, manifest = checkpoints.restore(d, 1, {"params": params, "opt": opt})
    s2 = TokenStream(cfg, 2, 16)
    s2.load_state_dict(manifest["meta"]["stream"])
    assert s2.cursor == stream.cursor and s2.seed == 7
    b1, b2 = stream.next(), s2.next()
    np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                  np.asarray(b2["tokens"]))
