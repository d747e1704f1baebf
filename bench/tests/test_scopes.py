"""Device time by named scope (bench/scopes.py) and its readers, on a
synthetic reduced trace and a fake op->scope map."""
import collections
import sys

import pytest

from bench import common
from bench import scopes as scopes_lib
from bench import trace as tr

SCOPE_READERS = {
    "rhs_layout_time_share": "rhs.layout",
    "rk_substep_time_share": "solver.rk_substep",
    "rollout_self_time_share": "fleet.rollout",
    "policy_forward_time_share": "rollout.policy",
    "ppo_update_time_share": "fleet.update",
    "broker_push_time_share": "fleet.broker",
}

MAP = {
    "jit__step_impl": {
        "while.1": ("fleet.rollout", frozenset()),
        "fusion.1": ("rhs.layout", frozenset({"rhs.layout"})),
        "fusion.2": ("solver.rk_substep",
                     frozenset({"solver.rk_substep", "rhs.layout"})),
        "fusion.3": ("fleet.rollout", frozenset({"fleet.rollout"})),
        "fusion.4": ("rollout.policy", frozenset({"rollout.policy"})),
        "fusion.5": ("fleet.update", frozenset({"fleet.update"})),
        "copy.6": ("fleet.broker", frozenset()),
        "fused_ns_rhs.7": ("solver.rk_substep", frozenset()),
    },
    "jit_other": {"fusion.4": ("fleet.update", frozenset())},
}


def _reduced():
    """One plane; window (0, 20).  `while.1` spans (1, 15) and holds the
    rollout's operations; fusion.2 overlaps the kernel by 0.5 s; `add.9` is
    in no map; 16-18 is idle; fusion.5 runs past the window's end."""
    ops = {"/device:TPU:0": [
        ("while.1", "jit__step_impl", 1.0, 15.0),
        ("fusion.1", "jit__step_impl", 1.0, 3.0),
        ("fused_ns_rhs.7", "jit__step_impl", 3.0, 5.0),
        ("fusion.2", "jit__step_impl", 4.5, 6.0),
        ("fusion.3", "jit__step_impl", 6.0, 7.0),
        ("fusion.4", "jit__step_impl", 7.0, 10.0),
        ("copy.6", "jit__step_impl", 10.0, 15.0),
        ("add.9", "jit_keys", 0.0, 1.0),
        ("fusion.4", "jit_other", 15.0, 16.0),
        ("fusion.5", "jit__step_impl", 18.0, 22.0),
    ]}
    return tr.Reduced(window=(0.0, 20.0), ops=ops,
                      spans={tr.WINDOW_SPAN: [(0.0, 20.0)]})


def _ctx(reduced=None):
    return {"trace": reduced or _reduced(), "rhs_kernel": "fused_ns_rhs",
            "spans": common.Spans(False)}


@pytest.fixture
def fake_map(monkeypatch):
    monkeypatch.setattr(scopes_lib, "scope_map", lambda: MAP)


def test_seconds_by_scope_self_time_overlap_and_window(fake_map):
    secs = scopes_lib.seconds_by_scope(_reduced(), MAP, "fused_ns_rhs")
    assert secs == {
        None: pytest.approx(1.0),                 # add.9: in no map
        "rhs.layout": pytest.approx(2.0),
        scopes_lib.KERNEL: pytest.approx(2.0),
        "solver.rk_substep": pytest.approx(1.0),  # 0.5 s overlap not again
        "fleet.rollout": pytest.approx(1.0),      # fusion.3, not the loop
        "rollout.policy": pytest.approx(3.0),
        "fleet.broker": pytest.approx(5.0),
        "fleet.update": pytest.approx(1.0 + 2.0),  # other module; clipped
    }


@pytest.mark.parametrize("reader,scope", sorted(SCOPE_READERS.items()))
def test_scope_readers_read_their_scope(fake_map, reader, scope):
    secs = scopes_lib.seconds_by_scope(_reduced(), MAP, "fused_ns_rhs")
    got = common.load_reader(reader)(_ctx())
    assert got == pytest.approx(100.0 * secs[scope] / 20.0)


def test_unscoped_share_leaves_out_the_kernel(fake_map):
    assert common.load_reader("unscoped_device_share")(_ctx()) == \
        pytest.approx(100.0 * 1.0 / 20.0)


def test_shares_add_up_to_the_busy_share(fake_map):
    ctx = _ctx()
    total = sum(common.load_reader(r)(ctx) for r in SCOPE_READERS)
    total += common.load_reader("rhs_kernel_time_share")(ctx)
    total += common.load_reader("unscoped_device_share")(ctx)
    idle = common.load_reader("device_idle_share.train")(ctx)
    assert idle == pytest.approx(10.0)
    assert total == pytest.approx(100.0 - idle)


def test_an_op_joins_the_module_its_label_names():
    assert scopes_lib.lookup(MAP, "fusion.4", "x jit_other y")[0] == \
        "fleet.update"
    assert scopes_lib.lookup(MAP, "fusion.4", "")[0] == "rollout.policy"
    assert scopes_lib.lookup(MAP, "fusion.99", "jit__step_impl") is None


def test_readers_read_nothing_without_a_map(monkeypatch):
    monkeypatch.setattr(scopes_lib, "scope_map", lambda: None)
    for r in [*SCOPE_READERS, "unscoped_device_share"]:
        assert common.load_reader(r)(_ctx()) is None
    assert common.load_reader("unscoped_device_share")(
        {"trace": None}) is None


def test_a_scope_the_program_lacks_reads_nothing(monkeypatch):
    monkeypatch.setattr(scopes_lib, "scope_map", lambda: {
        "m": {"fusion.1": ("fleet.update", frozenset())}})
    assert common.load_reader("ppo_update_time_share")(_ctx()) == \
        pytest.approx(10.0)
    assert common.load_reader("rhs_layout_time_share")(_ctx()) is None


def test_a_program_without_obs_reads_nothing(monkeypatch):
    import repro
    monkeypatch.delattr(repro, "obs", raising=False)
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert scopes_lib.scope_map() is None
    for r in [*SCOPE_READERS, "unscoped_device_share", "host_dispatch_ms"]:
        assert common.load_reader(r)(_ctx()) is None


def test_host_dispatch_ms_reads_the_program_spans_inside_the_window(
        monkeypatch):
    from repro import obs
    monkeypatch.setattr(obs, "_SPANS", collections.deque([
        ("fleet.dispatch", 1.0, 1.5),        # before the window
        ("fleet.dispatch", 10.0, 10.002),
        ("other", 11.0, 12.0),
        ("fleet.dispatch", 20.0, 20.004),
        ("fleet.dispatch", 29.9, 30.5),      # ends after it
    ]))
    ctx = _ctx()
    ctx["spans"].spans["bench.window"] = [(5.0, 30.0)]
    assert common.load_reader("host_dispatch_ms")(ctx) == pytest.approx(3.0)
    ctx["spans"].spans["bench.window"] = [(40.0, 50.0)]
    assert common.load_reader("host_dispatch_ms")(ctx) is None
