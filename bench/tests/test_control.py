"""The control fails what sound runs pass: at a test size, the program's
bfloat16 rollout path, the reference's planted faults and the serving
control each read above a limit."""
import tempfile

import pytest

from bench import common, control
from conftest import DATA, TRAIN_CELLS, tiny_traffic


def _over(gaps: dict, limits: dict) -> list[str]:
    return [k for k, v in gaps.items() if not v <= limits[k]]


@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_training_control_and_faults_fail(cell):
    config = common.load_json(DATA / "hit_tiny.json")
    limits = common.load_json(common.limits_path(cell))
    out = control.train_readings(config, tiny_traffic(cell), 5,
                                 tempfile.mkdtemp())
    for label in ("control_bf16", "state_unchanged", "half_batch",
                  "reward_altered"):
        assert _over(out[label], limits), (label, out[label])


def test_serving_control_fails():
    config = common.load_json(DATA / "hit_tiny.json")
    traffic = common.load_json(DATA / "serve_tiny.json")
    limits = common.load_json(DATA / "serve_limits.json")
    out = control.serve_readings(config, traffic, 5)
    assert _over(out["control_bf16"], limits), out
    assert _over(out["answer_altered"], limits), out
