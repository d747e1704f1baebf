"""What every cell shares: the manifest and the files it names, the host
spans, the compile counter, the per-layer readers and the result line."""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SPAN_NAMES = ("bench.window", "iteration.dispatch", "iteration.wait",
              "serve.submit", "serve.flush", "generator.wait")


def seed_key(seed: int):
    """A PRNG key from a seed of any size: the low 31 bits seed it, the
    rest is folded in."""
    import jax
    key = jax.random.PRNGKey(seed % 2**31)
    return jax.random.fold_in(key, (seed // 2**31) % 2**31)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def config_path(name: str) -> Path:
    return BENCH / "configs" / f"{name}.json"


def traffic_path(name: str) -> Path:
    return BENCH / "traffic" / f"{name}.json"


def limits_path(workload: str) -> Path:
    return BENCH / "limits" / f"{workload}.json"


def reader_path(metric: str) -> Path:
    return BENCH / "metrics" / f"{metric}.py"


def validate(manifest: dict) -> None:
    """Every name in the manifest resolves to its file; every per-layer
    metric names its cells, and moves an end-to-end metric that each of
    them reports; every cell reports a per-layer metric.  An end-to-end
    metric without a `workloads` list reaches every cell, so a new cell
    joins by new entries and new files alone."""
    errors = []
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    cells = {w["name"]: w for w in manifest["workloads"]}
    configs = {c["name"] for c in manifest["configs"]}

    def reports(cell: str, metric: str) -> bool:
        ws = e2e[metric].get("workloads")
        return ws is None or cell in ws

    for c in manifest["configs"]:
        if not (ROOT / c["file"]).is_file():
            errors.append(f"config {c['name']}: no file {c['file']}")
    for w in manifest["workloads"]:
        if w["config"] not in configs:
            errors.append(f"cell {w['name']}: unknown config {w['config']}")
        if not config_path(w["config"]).is_file():
            errors.append(f"cell {w['name']}: no {config_path(w['config'])}")
        if not traffic_path(w["traffic"]).is_file():
            errors.append(f"cell {w['name']}: no "
                          f"{traffic_path(w['traffic'])}")
        if not limits_path(w["name"]).is_file():
            errors.append(f"cell {w['name']}: no {limits_path(w['name'])}")
    listed = {c for m in manifest["per_layer"] for c in m.get("workloads", [])}
    for cell in cells.keys() - listed:
        errors.append(f"cell {cell}: reports no per-layer metric")
    for m in manifest["per_layer"]:
        if not reader_path(m["name"]).is_file():
            errors.append(f"metric {m['name']}: no reader "
                          f"{reader_path(m['name'])}")
        if m["moves"] not in e2e:
            errors.append(f"metric {m['name']}: moves unknown end-to-end "
                          f"metric {m['moves']}")
            continue
        if "workloads" not in m:
            errors.append(f"metric {m['name']}: names no cells "
                          f"(a `workloads` list)")
            continue
        for cell in m["workloads"]:
            if cell not in cells:
                errors.append(f"metric {m['name']}: unknown cell {cell}")
            elif not reports(cell, m["moves"]):
                errors.append(f"metric {m['name']}: cell {cell} does not "
                              f"report {m['moves']}")
    if errors:
        raise SystemExit("BENCHMARK.json does not resolve:\n  "
                         + "\n  ".join(errors))


def cell_metrics(manifest: dict, cell: str) -> tuple[list, list]:
    """(end-to-end, per-layer) metric entries that `cell` reports."""
    e2e = [m for m in manifest["end_to_end"]
           if cell in m.get("workloads", [cell])]
    per_layer = [m for m in manifest["per_layer"] if cell in m["workloads"]]
    return e2e, per_layer


def load_reader(metric: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", reader_path(metric))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileClock:
    """Backend compilations (JAX's `backend_compile_duration` events):
    their count and seconds."""

    def __init__(self):
        self.count, self.secs = 0, 0.0

    def __call__(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.secs += secs


class Spans:
    """Host spans around the benchmark's own calls: kept in memory, and
    written into the profiler's trace when one is being taken."""

    def __init__(self, tracing: bool):
        self.tracing = tracing
        self.spans: dict[str, list[tuple[float, float]]] = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        ann = contextlib.nullcontext()
        if self.tracing:
            import jax
            ann = jax.profiler.TraceAnnotation(name)
        with ann:
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.spans.setdefault(name, []).append(
                    (t0, time.perf_counter()))

    def total(self, name: str) -> tuple[float, int]:
        ivs = self.spans.get(name, [])
        return sum(e - s for s, e in ivs), len(ivs)


@dataclasses.dataclass
class Check:
    """One compared number and its limit: passes when value <= limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    """What a driver hands back to run.py."""

    attempted: int
    failed: int
    e2e: dict                      # end-to-end metric name -> value
    checks: list
    memory_peak_bytes: int
    context: dict                  # what the per-layer readers read
    notes: dict = dataclasses.field(default_factory=dict)


def stderr(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
