"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name from BENCHMARK.json: its
configuration `bench/configs/<config>.json`, its traffic mix
`bench/traffic/<traffic>.json` (whose `driver` key names the general
driver in `bench/drivers/`), the limits of its compared numbers
`bench/limits/<cell>.json`, and one reader per per-layer metric
`bench/metrics/<metric>.py`.  The run warms up the cell's own shapes
(set-up), measures for `--seconds`, checks what the timed path produced
against the plain reference under `bench/reference/`, and prints one JSON
line last on standard output.  With `--trace 1` the window is traced and
the line carries the per-layer metrics instead of the end-to-end ones.
Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import common  # noqa: E402
from bench.common import stderr  # noqa: E402


class Harness:
    """What a driver gets: the cell's files, the seed, the window, the
    spans, the compile counter and the profiler switch."""

    def __init__(self, args, manifest, cell):
        self.cell = cell
        self.config = common.load_json(common.config_path(cell["config"]))
        self.traffic = common.load_json(common.traffic_path(cell["traffic"]))
        self.limits = common.load_json(common.limits_path(cell["name"]))
        self.seed = args.seed
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.spans = common.Spans(self.trace)
        self.clock = common.CompileClock()
        self.trace_dir = None
        self.t_window = None
        self.tmp = tempfile.mkdtemp(prefix="bench_")
        self.seed_key = common.seed_key(args.seed)

    @contextlib.contextmanager
    def profile(self):
        """The measured window; traced with --trace 1."""
        import jax
        self.t_window = time.perf_counter()
        if not self.trace:
            yield
            return
        self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(self.trace_dir)
        try:
            yield
        finally:
            jax.profiler.stop_trace()

    @staticmethod
    def memory_peak() -> int:
        import jax
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in jax.local_devices())


def enable_compile_cache() -> None:
    """Persistent compile cache at a fixed path inside the checkout, or
    where JAX_COMPILATION_CACHE_DIR points; every program is cached."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def per_layer(h, manifest, outcome, peaks) -> dict:
    from bench import trace as trace_lib

    reduced = trace_lib.reduce(h.trace_dir, common.SPAN_NAMES)
    stderr(json.dumps({"trace_planes": {p: len(v) for p, v in
                                        reduced.ops.items()},
                       "window": reduced.window,
                       "first_ops": [o[:3] for v in reduced.ops.values()
                                     for o in v[:5]]}))
    ctx = dict(outcome.context, trace=reduced, spans=h.spans, peaks=peaks,
               chips=h.cell["chips"], e2e=outcome.e2e)
    _, layers = common.cell_metrics(manifest, h.cell["name"])
    out = {}
    for m in layers:
        value = common.load_reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out, reduced


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    manifest = common.load_json(ROOT / "BENCHMARK.json")
    common.validate(manifest)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if args.workload not in cells:
        stderr(f"unknown workload {args.workload!r}; have {sorted(cells)}")
        return 2
    cell = cells[args.workload]

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        stderr(f"needs {cell['chips']} TPU chip(s); found {len(devices)} "
               f"{devices[0].platform} device(s)")
        return 2
    peaks_all = common.load_json(common.BENCH / "peaks.json")
    kind = devices[0].device_kind
    if kind not in peaks_all:
        stderr(f"no peaks for device kind {kind!r} in bench/peaks.json")
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import repro  # noqa: F401  (the system under test must be present)

    enable_compile_cache()
    # float32 matrix products at float32 precision, as the configurations
    # state (the TPU default is one bfloat16 pass)
    jax.config.update("jax_default_matmul_precision", "highest")
    h = Harness(args, manifest, cell)
    jax.monitoring.register_event_duration_secs_listener(h.clock)
    driver = importlib.import_module(f"bench.drivers.{h.traffic['driver']}")
    try:
        return report(h, manifest, driver.run(h), devices, kind,
                      peaks_all[kind])
    finally:
        for d in (h.tmp, h.trace_dir):
            if d:
                shutil.rmtree(d, ignore_errors=True)


def report(h, manifest, outcome, devices, kind, peaks) -> int:
    """Print the result line (and the compared numbers on stderr)."""
    cell = h.cell
    setup_s = h.t_window - T_START

    e2e_entries, _ = common.cell_metrics(manifest, cell["name"])
    device = {"platform": devices[0].platform, "kind": kind,
              "count": cell["chips"],
              "memory_peak_bytes": outcome.memory_peak_bytes}
    result = {"correct": all(c.ok for c in outcome.checks),
              "attempted": outcome.attempted, "failed": outcome.failed}
    if h.trace:
        metrics, reduced = per_layer(h, manifest, outcome, peaks)
        device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = {"device_ops": reduced.top_ops(),
                               "idle_gaps": reduced.top_gaps()}
    else:
        values = dict(outcome.e2e, setup_s=setup_s)
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in e2e_entries}
        result["device"] = device
    stderr(json.dumps({"setup_s": setup_s, "compile_s": h.clock.secs,
                       "compiles": h.clock.count, **outcome.notes}))
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in outcome.checks}
    for c in outcome.checks:
        stderr(f"check {c.name} = {c.value!r} (limit {c.limit!r}) "
               f"{'ok' if c.ok else 'FAILED'}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
