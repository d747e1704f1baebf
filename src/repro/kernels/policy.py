"""Backend-driven kernel defaults — the single policy every entry point uses.

The Pallas kernels are the production path on TPU and an interpret-mode
oracle-check everywhere else.  Rather than each call site hardcoding
`interpret=True` (which silently de-optimizes real TPU runs) or configs
hardcoding `use_kernels=False` (which leaves the fused path dead on TPU),
both questions resolve here from `jax.default_backend()`:

  * `default_impl()`      "kernel" on TPU, "ref" elsewhere — what
                          `HITConfig`/`ChannelConfig` use when their
                          `use_kernels` field is left at None (auto).
  * `default_interpret()` False on TPU (compile for real), True elsewhere
                          (Pallas interprets; same numerics, any backend) —
                          what every kernel's `interpret=None` resolves to.

A `REPRO_KERNELS={kernel,ref,auto}` environment variable overrides the
*auto* resolution only — it retargets every `use_kernels=None` config and
`impl=None` call without editing code (benchmarks/CI forcing one column),
while an explicit config choice (`use_kernels=True/False`, `impl=...`)
still wins.  The variable is read at trace time: set it before the first
jit of a config, since cached programs keep the policy they traced with.

This module is a leaf (imports jax + os only) so the kernel modules
themselves can use it without cycling through the package __init__.
"""
from __future__ import annotations

import os

import jax

_ENV_VAR = "REPRO_KERNELS"
ACCEPTED = ("kernel", "ref", "auto")


def _env_override() -> str | None:
    raw = os.environ.get(_ENV_VAR, "")
    val = raw.strip().lower()
    if not val or val == "auto":
        return None
    if val in ("kernel", "ref"):
        return val
    raise ValueError(
        f"invalid {_ENV_VAR}={raw!r}: accepted values are "
        f"{', '.join(repr(a) for a in ACCEPTED)} ('auto' and unset both "
        "mean backend policy: kernels compiled on TPU, reference jnp "
        "elsewhere)")


# Fail at import, not at the first kernel dispatch deep inside a trace: a
# typo'd REPRO_KERNELS in a batch script should kill the job immediately
# with the accepted set, not after minutes of setup.
_env_override()


def default_impl() -> str:
    """Implementation the configs pick when `use_kernels` is None (auto):
    the REPRO_KERNELS env override if set, else the backend policy."""
    override = _env_override()
    if override is not None:
        return override
    return "kernel" if jax.default_backend() == "tpu" else "ref"


def default_interpret() -> bool:
    """Pallas interpret mode: compiled on TPU, interpreted everywhere else."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """An explicit `interpret` wins; None defers to the backend policy."""
    return default_interpret() if interpret is None else interpret


# Mosaic's default scoped-VMEM limit on TPU v5e.
DEFAULT_SCOPED_VMEM = 16 * 2**20


def scoped_vmem_limit(need_bytes: int) -> int | None:
    """`vmem_limit_bytes` for a kernel whose working set is `need_bytes`:
    None (the compiler default) when the default suffices."""
    return None if need_bytes <= DEFAULT_SCOPED_VMEM else int(need_bytes)


def resolve_use_kernels(use_kernels: bool | None) -> bool:
    """Config `use_kernels` field: an explicit choice wins; None = policy.
    The shared resolver behind HITConfig/ChannelConfig `.kernels_enabled`."""
    return default_impl() == "kernel" if use_kernels is None else use_kernels
