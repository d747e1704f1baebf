"""Distribution tests that run on the single real device: logical-axis
rules, flash-decode combine vs the oracle, compressed collectives, and the
orchestrator's fleet layout."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.models import api, attention
from repro.parallel import sharding as shd
from repro.launch import mesh as mesh_lib


def test_default_rules_cover_model_axes():
    for name in ("batch", "embed", "heads", "mlp", "experts", "vocab",
                 "kv_seq", "act_seq"):
        assert name in shd.DEFAULT_RULES


def test_constrain_noop_without_context():
    x = jnp.ones((4, 4))
    y = shd.constrain(x, "batch", None)
    assert y is x


def test_constrain_applies_spec_on_mesh():
    mesh = mesh_lib.auto_mesh((1, 1), ("data", "model"))
    with mesh, shd.axis_rules(mesh):
        y = jax.jit(lambda x: shd.constrain(x, "batch", "mlp"))(
            jnp.ones((4, 8)))
    assert y.shape == (4, 8)


def test_param_specs_2d_weight():
    mesh = mesh_lib.auto_mesh((1, 1), ("data", "model"))
    rules = shd.AxisRules(mesh)
    params = {"w": jnp.ones((8, 16))}
    axes = {"w": ("embed", "mlp")}
    specs = shd.param_specs(params, axes, rules)
    assert specs["w"] == P("data", "model")


def test_param_specs_nondivisible_falls_back():
    # AbstractMesh: divisibility logic only needs mesh.shape
    mesh = jax.sharding.AbstractMesh((1, 2), ("data", "model"))
    rules = shd.AxisRules(mesh)
    specs = shd.param_specs({"w": jnp.ones((8, 25))}, {"w": ("embed", "heads")},
                            rules)
    assert specs["w"] == P("data", None)  # 25 heads don't divide model=2


def test_flash_decode_combine_matches_oracle():
    """decode_combine="flash" (shard_map partial-softmax merge) must equal
    the dense decode path."""
    cfg = dataclasses.replace(configs.get_reduced("h2o-danube-1.8b"),
                              dtype="float32", window=0, window_pattern=0,
                              decode_combine="flash")
    params_a = attention.init(jax.random.PRNGKey(0), cfg)
    b, s = 2, 8
    x = jax.random.normal(jax.random.PRNGKey(1), (b, 1, cfg.d_model),
                          jnp.float32)
    cache = attention.init_cache(cfg, b, s, window=None, dtype=jnp.float32)
    # warm the cache with some keys
    kx = jax.random.normal(jax.random.PRNGKey(2), (b, cfg.kv_heads, s, cfg.hd))
    vx = jax.random.normal(jax.random.PRNGKey(3), (b, cfg.kv_heads, s, cfg.hd))
    cache = {"k": kx.at[:, :, 5:].set(0), "v": vx.at[:, :, 5:].set(0),
             "pos": jnp.asarray(5, jnp.int32)}

    mesh = mesh_lib.auto_mesh((1, 1), ("data", "model"))
    with mesh, shd.axis_rules(mesh):
        out_flash, c1 = jax.jit(
            lambda p, x, c: attention.decode_attention(
                p, cfg, x, c, window=None, combine="flash"))(params_a, x, cache)
    out_dense, c2 = jax.jit(
        lambda p, x, c: attention.decode_attention(
            p, cfg, x, c, window=None, combine="allgather"))(params_a, x, cache)
    np.testing.assert_allclose(np.asarray(out_flash), np.asarray(out_dense),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(c1["k"]), np.asarray(c2["k"]))


def test_lower_cell_on_host_mesh():
    """specs.lower_cell works on an arbitrary (1,1) mesh — the dry-run path
    minus the 512-device override."""
    from repro.configs.shapes import ShapeConfig
    from repro.launch import specs
    cfg = configs.get_reduced("h2o-danube-1.8b")
    shape = ShapeConfig("tiny_train", 64, 4, "train")
    mesh = mesh_lib.auto_mesh((1, 1), ("data", "model"))
    lowered, meta = specs.lower_cell(cfg, shape, mesh)
    compiled = lowered.compile()
    assert compiled.cost_analysis()["flops"] > 0
    shape_d = ShapeConfig("tiny_decode", 64, 4, "decode")
    lowered, _ = specs.lower_cell(cfg, shape_d, mesh)
    assert lowered.compile() is not None


def test_orchestrator_sharded_fleet():
    from repro.configs import relexi_hit
    from repro.core.orchestrator import FleetConfig, Orchestrator
    mesh = mesh_lib.auto_mesh((1,), ("data",))
    orch = Orchestrator(relexi_hit.reduced(), FleetConfig(n_envs=2, bank_size=3),
                        mesh=mesh)
    traj = orch.sample_fleet(orch.params_placeholder, jax.random.PRNGKey(0)) \
        if hasattr(orch, "params_placeholder") else None
    # minimal: bank is placed and initial draws respect the env sharding
    u0 = orch.draw_initial_states(jax.random.PRNGKey(0))
    assert u0.shape[0] == 2
    assert bool(jnp.all(jnp.isfinite(u0)))


def test_collective_bytes_parser():
    from repro.launch import hlo_analysis
    hlo = """
  %p = f32[16,128]{1,0} parameter(0)
  %ag = f32[16,2048]{1,0} all-gather(%p), replica_groups={}
  %ar = f32[16,128]{1,0} all-reduce(%p), to_apply=%add
  %cp = f32[16,128]{1,0} collective-permute(%p), source_target_pairs={{0,1}}
"""
    stats = hlo_analysis.collective_bytes(hlo)
    assert stats.count_by_kind["all-gather"] == 1
    assert stats.bytes_by_kind["all-gather"] == 16 * 2048 * 4
    assert stats.bytes_by_kind["all-reduce"] == 2 * 16 * 128 * 4
    assert stats.bytes_by_kind["collective-permute"] == 16 * 128 * 4


def test_roofline_terms_math():
    from repro.launch import hlo_analysis
    t = hlo_analysis.roofline_terms(
        flops_per_dev=197e12, hbm_bytes_per_dev=0.0, coll_bytes_per_dev=0.0,
        n_chips=1, peak_flops=197e12, hbm_bw=819e9, link_bw=50e9)
    assert t["bound"] == "compute"
    assert t["roofline_fraction"] == pytest.approx(1.0)


_FOUR_DEVICE_WORKER = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=4")
import jax
from repro.fleet import make_fleet_runner, scheduler
from repro.fleet.pipeline import FleetRunnerConfig
from repro.launch import mesh as mesh_lib

mesh = mesh_lib.make_fleet_mesh()
assert dict(mesh.shape) == {"data": 4, "model": 1}, mesh.shape
runner = make_fleet_runner(
    ("hit_les_reduced",), total_envs=64, mesh=mesh,
    run_cfg=FleetRunnerConfig(checkpoint_dir="unused", bank_size=4),
    use_artifacts=False)
keys = {"hit_les_reduced": scheduler.rollout_key(runner.seed_key, 0, 0)}
padded = jax.jit(runner.program.rollout_super_batch)(runner.params, keys)
rewards = padded["hit_les_reduced"].rewards
shards = sorted((s.device.id, s.data.shape[1])
                for s in rewards.addressable_shards)
assert shards == [(d, 16) for d in range(4)], shards
print("four devices ok")
"""


def test_fleet_mesh_places_16_envs_per_device_on_four_devices():
    """`make_fleet_mesh` puts all four devices on `data`, and the fleet's
    64-env rollout gives each device 16 envs (CPU virtual devices, fresh
    subprocess: the device count is fixed at backend start-up)."""
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    proc = subprocess.run([sys.executable, "-c", _FOUR_DEVICE_WORKER],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "four devices ok" in proc.stdout
