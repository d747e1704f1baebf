"""bench/run.py refuses to measure anything but a TPU, resolves every name
of the manifest, and fails loudly on one that does not resolve."""
import copy
import os
import subprocess
import sys

import pytest

from bench import common


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
    common.validate(common.load_json(common.ROOT / "BENCHMARK.json"))


def test_unresolved_names_are_errors():
    m = common.load_json(common.ROOT / "BENCHMARK.json")
    bad = copy.deepcopy(m)
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


def test_every_reader_returns_nothing_without_a_trace():
    ctx = {"trace": None, "spans": common.Spans(False), "peaks": {},
           "chips": 1, "iterations": 0, "batches": 0,
           "generator_lag_ms": []}
    for m in common.load_json(common.ROOT / "BENCHMARK.json")["per_layer"]:
        assert common.load_reader(m["name"])(ctx) is None, m["name"]
