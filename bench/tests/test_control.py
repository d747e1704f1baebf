"""The control fails what sound runs pass: at a test size, the program's
bfloat16 rollout path, the reference's planted faults and the serving
control each read above a limit."""
import tempfile

from bench import common, control
from conftest import DATA


def _over(gaps: dict, limits: dict) -> list[str]:
    return [k for k, v in gaps.items() if not v <= limits[k]]


def test_training_control_and_faults_fail():
    config = common.load_json(DATA / "hit_tiny.json")
    traffic = common.load_json(DATA / "train_tiny.json")
    limits = common.load_json(common.limits_path("hit24_train_fleet64"))
    out = control.train_readings(config, traffic, 5, tempfile.mkdtemp())
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
