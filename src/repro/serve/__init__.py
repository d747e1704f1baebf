"""Trained-controller serving: batched low-latency inference for fleet
checkpoints.

Training (`fleet/pipeline.py`) produces one multitask parameter tree —
shared trunk + per-scenario adapters/heads — and checkpoints it together
with the optimizer and broker state.  This package is the other half of
the paper's HPC story: any solver, anywhere, calls the trained
eddy-viscosity controllers as a service (SmartFlow's solver-agnostic
deployment framing).  Three layers:

  * `loader`  — restore ONLY the policy subtree from a fleet checkpoint
                (the optimizer moments and broker rings stay on disk) and
                rebuild the `MultiTaskConfig` from the checkpoint's own
                metadata, optionally re-placing the tree on a serving mesh
                that need not match the training mesh
                (`core/elastic.reshard` — the preemption/restore path);
  * `batcher` — pad heterogeneous per-scenario request queues to a fixed
                ladder of compiled batch buckets, preserving per-request
                order, with slot recycling for streaming callers;
  * `service` — route requests by registered scenario name through ONE
                jitted `serve_step` per (scenario, batch-bucket):
                deterministic greedy actions (`multitask.actor_mean`, the
                exact training-time evaluation path — served actions are
                bit-identical to `Orchestrator.evaluate`'s at fp32) with a
                donated on-device request-counter buffer.

The `serve_step` entry point is registered in `analysis/entrypoints.py`
so repro-lint gates its donation/f64 invariants; the benchmark's
open-loop driver (`bench/drivers/serve.py`) measures its latency.
"""
from .batcher import (DEFAULT_BUCKETS, PendingBatch, RequestBatcher,
                      bucket_for)
from .loader import LoadedPolicy, load_policy
from .service import ControllerService, ServeResult, load_service

__all__ = [
    "DEFAULT_BUCKETS",
    "PendingBatch",
    "RequestBatcher",
    "bucket_for",
    "LoadedPolicy",
    "load_policy",
    "ControllerService",
    "ServeResult",
    "load_service",
]
