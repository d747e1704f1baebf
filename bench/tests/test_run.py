"""bench/run.py refuses to measure anything but a TPU, resolves every name
of the manifest, and fails loudly on one that does not resolve."""
import copy
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import common, compare

MANIFEST = common.load_json(common.ROOT / "BENCHMARK.json")


def test_exits_non_zero_without_a_tpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(common.BENCH / "run.py"), "--workload",
         "hit24_train_fleet64", "--seed", str(2**31 + 5), "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=common.ROOT, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def test_manifest_resolves():
    common.validate(MANIFEST)


def test_unresolved_names_are_errors():
    bad = copy.deepcopy(MANIFEST)
    # a cell outside an end-to-end metric's list does not report it
    e2e = {e["name"]: e for e in bad["end_to_end"]}
    e2e["env_steps_per_s"]["workloads"] = ["hit24_train_fleet64"]
    bad["workloads"].append(dict(bad["workloads"][0], name="new_cell",
                                 traffic="no_such_mix"))
    bad["per_layer"].append(dict(bad["per_layer"][0], name="no_reader",
                                 moves="env_steps_per_s"))
    bad["per_layer"].append(dict(bad["per_layer"][0], name="train_step_mfu",
                                 moves="env_steps_per_s",
                                 workloads=["new_cell"]))
    with pytest.raises(SystemExit) as err:
        common.validate(bad)
    msg = str(err.value)
    assert "no_such_mix" in msg and "no_reader" in msg
    assert "does not report env_steps_per_s" in msg
    assert "new_cell" in msg


def test_a_training_cell_joins_by_new_entries_alone(tmp_path, monkeypatch):
    """A new cell on an existing configuration and traffic mix, with its
    own limits file and one per-layer metric of its own (a reader that
    reuses another's), both here at a temporary path; no entry touched."""
    m = copy.deepcopy(MANIFEST)
    name, metric = "hit24_train_fleet64_again", "train_step_mfu.again"
    limits, reader = common.limits_path, common.reader_path
    (tmp_path / f"{name}.json").write_text(
        limits("hit24_train_fleet64").read_text())
    (tmp_path / f"{metric}.py").write_text(reader("train_step_mfu")
                                           .read_text())
    monkeypatch.setattr(common, "limits_path", lambda w: (
        tmp_path / f"{w}.json" if w == name else limits(w)))
    monkeypatch.setattr(common, "reader_path", lambda x: (
        tmp_path / f"{x}.py" if x == metric else reader(x)))
    m["workloads"].append(dict(m["workloads"][0], name=name))
    with pytest.raises(SystemExit, match=f"cell {name}: reports no "
                                         f"per-layer metric"):
        common.validate(m)
    entry = dict(m["per_layer"][1], name=metric, workloads=[name])
    m["per_layer"].append(entry)
    common.validate(m)
    e2e, per_layer = common.cell_metrics(m, name)
    assert {e["name"] for e in e2e} == {"env_steps_per_s", "setup_s"}
    assert per_layer == [entry]
    assert common.load_reader(metric)({"trace": None}) is None
    assert all(m[k] == MANIFEST[k] for k in ("configs", "end_to_end"))
    assert m["per_layer"][:-1] == MANIFEST["per_layer"]


def test_per_layer_metric_without_cells_is_an_error():
    bad = copy.deepcopy(MANIFEST)
    del bad["per_layer"][0]["workloads"]
    with pytest.raises(SystemExit) as err:
        common.validate(bad)
    assert f"metric {bad['per_layer'][0]['name']}: names no cells" in str(
        err.value)


def _compared_numbers(traffic: dict) -> set[str]:
    """The names of the numbers a cell compares with its limits, as the
    gap functions that produce them name them."""
    if traffic["driver"] == "train":
        tree = {"w": np.ones(3)}
        steps = traffic["steps_compared"]
        return set(compare.training_gaps(
            losses_got=[1.0] * steps, losses_want=[1.0] * steps,
            m_got=tree, m_want=tree, p0=tree, p_got=tree, p_want=tree))
    from bench.drivers import serve
    return set(serve.answer_gaps({}, np.arange(1), np.ones((1, 2)),
                                 np.ones(1), 1.0))


@pytest.mark.parametrize("cell", MANIFEST["workloads"],
                         ids=lambda w: w["name"])
def test_limits_name_exactly_the_compared_numbers(cell):
    traffic = common.load_json(common.traffic_path(cell["traffic"]))
    limits = common.load_json(common.limits_path(cell["name"]))
    assert set(limits) == _compared_numbers(traffic)
    assert all(v > 0 for v in limits.values()), limits


def test_every_reader_returns_nothing_without_a_trace():
    ctx = {"trace": None, "spans": common.Spans(False), "peaks": {},
           "chips": 1, "iterations": 0, "batches": 0,
           "generator_lag_ms": []}
    for m in MANIFEST["per_layer"]:
        assert common.load_reader(m["name"])(ctx) is None, m["name"]
