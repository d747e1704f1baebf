"""Production mesh construction + the multi-host entry path.

A function (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax use,
and everything else must see the real (single) device.

Topology: TPU v5e, 256 chips/pod (16x16 ICI torus), 2 pods over DCN.
  single pod : (data=16, model=16)
  multi pod  : (pod=2, data=16, model=16)

The `pod` axis is the slow (DCN) axis: only data parallelism (env batches /
LM batches) and gradient reduction cross it (core/compression.py compresses
that hop).  `model` is the fast ICI axis used for tensor/expert/sequence
parallelism.

Multi-host: `init_distributed` is the guarded `jax.distributed.initialize`
entry (idempotent, env-var driven, no-op for single-process runs) and
`make_fleet_mesh` builds the process-spanning (data, model) mesh from
`jax.devices()` — which enumerates GLOBAL devices once the distributed
runtime is up.  The fleet's single program (`fleet/superbatch.py`) runs
unmodified over that mesh on backends whose runtime supports cross-process
computations (TPU/GPU).  The CPU PJRT backend does not ("Multiprocess
computations aren't implemented on the CPU backend"), so the 2-process CPU
smoke test and the per-host scaling benchmark rows run each process's
LOCAL shard of the collective-free rollout region instead — see
`make_local_mesh` and tests/test_fleet_distributed.py.
"""
from __future__ import annotations

import os

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...], *,
              devices=None) -> Mesh:
    """`jax.make_mesh` with *Auto* axes.  jax.make_mesh defaults to
    Explicit axes, on which `with_sharding_constraint` and the fleet's
    `NamedSharding` placements are refused; every mesh here is Auto."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def _split_data_model(n: int) -> tuple[int, int]:
    """(data, model) factorization of `n` devices: the largest model width
    in {4, 2, 1} that divides evenly; the rest is data parallelism."""
    for model in (4, 2, 1):
        if n % model == 0:
            return n // model, model
    return n, 1


def make_host_mesh():
    """Whatever devices exist, as a (data, model) mesh — tests / examples."""
    data, model = _split_data_model(len(jax.devices()))
    return auto_mesh((data, model), ("data", "model"))


def init_distributed(*, coordinator: str | None = None,
                     num_processes: int | None = None,
                     process_id: int | None = None) -> bool:
    """Guarded `jax.distributed.initialize` — the multi-host entry point.

    Reads the standard launcher variables (JAX_COORDINATOR_ADDRESS,
    JAX_NUM_PROCESSES, JAX_PROCESS_ID) when arguments are omitted; returns
    False without touching jax when they are absent (single-process run) or
    when the runtime is already initialized (idempotent re-entry, e.g. a
    benchmark calling through a runner that already initialized).  All
    jax device queries must happen AFTER this returns — `jax.devices()`
    enumerates the global mesh only once the coordinator handshake is done.
    """
    coordinator = coordinator or os.environ.get("JAX_COORDINATOR_ADDRESS")
    if coordinator is None:
        return False
    if num_processes is None:
        num_processes = int(os.environ.get("JAX_NUM_PROCESSES", "1"))
    if process_id is None:
        process_id = int(os.environ.get("JAX_PROCESS_ID", "0"))
    if num_processes <= 1:
        return False
    client = getattr(jax._src.distributed.global_state, "client", None)
    if client is not None:   # already initialized: keep the first init
        return True
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)
    return True


def make_fleet_mesh(*, model: int = 1):
    """Process-spanning (data, model) mesh over ALL devices — every process
    must call this with the same topology (jax.make_mesh uses the global
    device enumeration, identical on every process after
    `init_distributed`).  Data-major by default: the fleet's super-batch
    program shards env batches over `data` only, so every device goes to
    data parallelism unless a model width is requested explicitly."""
    n = len(jax.devices())
    if n % model:
        raise ValueError(f"model={model} does not divide {n} devices")
    return auto_mesh((n // model, model), ("data", "model"))


def make_local_mesh(*, model: int = 1):
    """This process's LOCAL devices as a (data, model) mesh — the shard a
    CPU multi-host process runs of the collective-free rollout region
    (cross-process programs need a TPU/GPU runtime; see module docstring).
    """
    local = jax.local_devices()
    if len(local) % model:
        raise ValueError(f"model={model} does not divide {len(local)} "
                         "local devices")
    return Mesh(np.asarray(local).reshape(len(local) // model, model),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)


# Hardware constants for the roofline terms (TPU v5e).
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link (~per-chip injection)
DCN_BW = 6.25e9                 # bytes/s per chip cross-pod (50 Gb/s)
