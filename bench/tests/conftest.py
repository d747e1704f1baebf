"""bench/tests run on the CPU at test sizes: `python -m pytest bench/tests`."""
import contextlib
import os
import sys
import tempfile
import time
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

DATA = Path(__file__).resolve().parent / "data"

from bench import common  # noqa: E402

CELLS = {w["name"]: w for w in
         common.load_json(ROOT / "BENCHMARK.json")["workloads"]}


def _traffic(cell: str) -> dict:
    return common.load_json(common.traffic_path(CELLS[cell]["traffic"]))


TRAIN_CELLS = [c for c in CELLS if _traffic(c)["driver"] == "train"]


def tiny_traffic(cell: str) -> dict:
    """The test-size training mix, comparing as many iterations as the
    cell's own traffic does."""
    return dict(common.load_json(DATA / "train_tiny.json"),
                steps_compared=_traffic(cell)["steps_compared"])


class TestHarness:
    """The driver-facing part of bench/run.py's Harness, without the chip."""

    def __init__(self, config, traffic, limits, seed=7, seconds=1.0):
        import jax

        self.cell = {"name": "test", "chips": 1}
        self.config = common.load_json(DATA / config)
        self.traffic = common.load_json(DATA / traffic)
        self.limits = limits
        self.seed = seed
        self.seed_key = jax.random.PRNGKey(seed)
        self.seconds = seconds
        self.trace = False
        self.spans = common.Spans(False)
        self.clock = common.CompileClock()
        jax.monitoring.register_event_duration_secs_listener(self.clock)
        self.tmp = tempfile.mkdtemp(prefix="bench_test_")
        self.t_window = None

    @contextlib.contextmanager
    def profile(self):
        self.t_window = time.perf_counter()
        yield

    @staticmethod
    def memory_peak():
        return 0


@pytest.fixture
def harness():
    return TestHarness
