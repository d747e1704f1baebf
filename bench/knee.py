"""Sweep a serving cell's offered rate once, to find its knee.

    python3 bench/knee.py --workload hit24_serve_poisson --seconds 10 \\
        --rates 1000 2000 4000

One process, one service: for each rate the open loop of
`bench/drivers/serve.py` runs for `--seconds`, and one JSON line reports
the latency percentiles and the backlog (requests due but not answered)
at each quarter of the window.  The knee is the highest rate whose backlog
at the window's end is no larger than at its start (zero) plus what is
due within one flush.  The cell's traffic file then fixes 0.8 x the knee;
the benchmark's runs never search for a rate.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import common  # noqa: E402
from bench.drivers import serve  # noqa: E402


class _Setup:
    def __init__(self, config, traffic, seed):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.seed_key = jax.random.PRNGKey(seed)
        self.tmp = tempfile.mkdtemp(prefix="bench_knee_")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    manifest = common.load_json(ROOT / "BENCHMARK.json")
    cell = {w["name"]: w for w in manifest["workloads"]}[args.workload]
    config = common.load_json(common.config_path(cell["config"]))
    traffic = common.load_json(common.traffic_path(cell["traffic"]))
    jax.config.update("jax_default_matmul_precision", "highest")
    _, _, pool, svc = serve.setup(_Setup(config, traffic, args.seed))
    name = config["registry"]
    for rate in args.rates:
        rng = np.random.default_rng(args.seed)
        due = serve.arrivals(rng, rate, args.seconds)
        picks = rng.integers(0, len(pool), len(due))
        spans = common.Spans(False)
        res = serve.offer(svc, name, pool, due, picks, spans, args.seconds)
        lat = (res["done"] - res["due"]) * 1e3
        backlog = [int(np.sum((res["due"] <= t) & ~(res["done"] <= t)))
                   for t in res["t0"] + args.seconds * np.array(
                       [0.25, 0.5, 0.75, 1.0])]
        flush_s, flushes = spans.total("serve.flush")
        print(json.dumps({
            "rate_per_s": rate, "requests": len(due),
            "refused": res["refused"], "backlog_quarters": backlog,
            "p50_ms": float(np.nanpercentile(lat, 50)),
            "p95_ms": float(np.nanpercentile(lat, 95)),
            "p99_ms": float(np.nanpercentile(lat, 99)),
            "flushes": flushes,
            "flush_ms": 1e3 * flush_s / max(flushes, 1)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
