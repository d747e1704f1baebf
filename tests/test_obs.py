"""repro.obs: host spans, and the map from compiled instructions to the
named scopes of the training step."""
import collections
import functools
import re
import time

import jax
import jax.numpy as jnp
import pytest

from repro import fleet, obs
from repro.cfd.solver import HITConfig
from repro.fleet.pipeline import FleetRunnerConfig

STEP_SCOPES = {"fleet.update", "fleet.broker", "fleet.rollout",
               "rollout.policy", "solver.rk_substep"}


def test_span_records_to_a_bounded_ring_and_nests(monkeypatch):
    monkeypatch.setattr(obs, "_SPANS", collections.deque(maxlen=3))
    with obs.span("outer"):
        with obs.span("inner"):
            time.sleep(0.001)
    got = obs.spans()
    assert [n for n, _, _ in got] == ["inner", "outer"]
    (_, i0, i1), (_, o0, o1) = got
    assert o0 <= i0 < i1 <= o1
    for k in range(5):
        with obs.span(f"s{k}"):
            pass
    assert [n for n, _, _ in obs.spans()] == ["s2", "s3", "s4"]


@pytest.mark.parametrize("op_name,scope", [
    ("jit(f)/fleet.rollout/shard_map/while/body/rollout.policy/dot_general",
     "rollout.policy"),
    ("jit(f)/transpose(jvp(fleet.update))/add_any", "fleet.update"),
    ("jit(f)/jit(g)/while/body/add", None),
    ("jit(f)/fleet.rollout/jit(_threefry_split)/FleetProgram.draw_padded_"
     "inputs", "fleet.rollout"),
    ("opt_state.step", None),      # an argument's name, not a scope
    (None, None),
])
def test_scope_is_the_innermost_dotted_component(op_name, scope):
    assert obs.scope_of(op_name) == scope


def test_op_scopes_maps_instructions_to_their_innermost_scope(monkeypatch):
    monkeypatch.setattr(obs, "_PROGRAMS", {})
    monkeypatch.setattr(obs, "_SCOPES", {})

    def f(w, x):
        with jax.named_scope("demo.outer"):
            y = jnp.tanh(x @ w)
            with jax.named_scope("demo.inner"):
                z = jnp.transpose(y) @ y
        return z, jnp.cos(x)

    w, x = jnp.ones((16, 8)), jnp.ones((4, 16))
    fn = jax.jit(f)
    obs.register_program("demo", fn, (w, x))
    text = fn.lower(w, x).compile().as_text()
    maps = obs.op_scopes()
    assert list(maps) == [text.split()[1].rstrip(",")]   # the module name
    (table,) = maps.values()
    assert {s for s, _ in table.values()} == {"demo.outer", "demo.inner"}
    for s, fused in table.values():     # a fusion's scope is its root's
        assert s in fused or not fused
    # the cosine is under no scope, so the map leaves it out
    cos = re.findall(r'%(\S+) = [^\n]*op_name="jit\(f\)/cos"', text)
    assert cos and not set(cos) & set(table)


def test_fused_rhs_layout_ops_map_to_rhs_layout(monkeypatch):
    from repro.kernels.rhs import fused_navier_stokes_rhs

    monkeypatch.setattr(obs, "_PROGRAMS", {})
    monkeypatch.setattr(obs, "_SCOPES", {})
    cfg = HITConfig(n_poly=2, n_elem=2, use_kernels=False)
    ops_d = cfg.operators()
    fn = jax.jit(functools.partial(
        fused_navier_stokes_rhs, block_e=2, interpret=True,
        inv_w_end=ops_d["inv_w_end"], jac=cfg.dg.jac,
        delta=cfg.delta_filter, mu=cfg.gas.mu, prandtl=cfg.prandtl,
        prandtl_turb=cfg.prandtl_turb, forcing_a0=cfg.forcing_a0,
        k_tke=cfg.k_tke))
    u = jnp.ones((3, 2, 2, 2, 3, 3, 3, 5))
    obs.register_program("rhs", fn, (u, jnp.full(u.shape[:-1], 0.17),
                                     ops_d["D"], ops_d["w"]))
    (table,) = obs.op_scopes().values()
    transposes = {n: v for n, v in table.items() if "transpose" in n}
    assert transposes
    assert all(s == "rhs.layout" for s, _ in transposes.values())
    # the kernel itself is under no scope: layout is the only one here
    assert {s for s, _ in table.values()} <= {"rhs.layout", None}
    assert set().union(*(f for _, f in table.values())) == {"rhs.layout"}


def test_fleet_program_registers_its_step_and_scopes(tmp_path, monkeypatch):
    monkeypatch.setattr(obs, "_PROGRAMS", {})
    monkeypatch.setattr(obs, "_SCOPES", {})
    runner = fleet.make_fleet_runner(
        ("hit_les_reduced",), total_envs=2, use_artifacts=False,
        run_cfg=FleetRunnerConfig(
            n_iterations=1, eval_every=100, checkpoint_every=100,
            checkpoint_dir=str(tmp_path), async_checkpoint=False,
            bank_size=4))
    runner.broker = runner.program.prologue(runner.params, runner.broker,
                                            runner._keys(0))
    assert obs._PROGRAMS == {}           # the prologue is not the step
    args = (runner.params, runner.opt_state, runner.broker,
            jnp.asarray(0, jnp.int32), runner._keys(1))
    want = runner.program._step.lower(*args).as_text()
    runner.run_iteration_pipelined(0)
    assert list(obs._PROGRAMS) == ["fleet.step"]
    jitted, shapes = obs._PROGRAMS["fleet.step"]
    # the stored shapes lower the very program that ran
    assert jitted.lower(*shapes).as_text() == want
    assert [n for n, _, _ in obs.spans()][-1] == "fleet.dispatch"
    (table,) = obs.op_scopes().values()
    scopes = {s for s, _ in table.values()}
    assert STEP_SCOPES <= scopes
    jax.block_until_ready(runner.params)
