"""Public kernel API: impl dispatch + differentiation glue.

Every op takes `impl`:
  None         resolved from `policy.default_impl()`: "kernel" on TPU,
               "ref" elsewhere (the solver configs' `use_kernels=None` auto).
  "kernel"     Pallas kernel, interpret mode auto-selected off-TPU (tests,
               CPU container), compiled on TPU.  Gradients: custom_vjp with
               recompute-from-ref backward (fwd speed where it matters; bwd
               correctness from the oracle — the backward kernels are listed
               as future work in DESIGN.md §Kernels).
  "ref"        the pure-jnp oracle from ref.py (solver ops).
  "chunked"    pure-jnp flash/chunk-equivalent (differentiable end-to-end,
               compilable on every backend) — the dry-run / training path.
  "naive"      full-materialization reference — tests and tiny shapes only.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from . import ref
from .dg_derivative import dg_derivative3 as _dg_pallas
from .flash_attention import flash_attention as _fa_pallas
from .linear_scan import linear_scan as _ls_pallas
from .policy import default_impl
from .rhs import fused_navier_stokes_rhs as _rhs_pallas
from .rhs import fused_navier_stokes_rhs_planar as _rhs_planar_pallas
from .rhs import fused_wall_rhs_planar as _wall_rhs_planar_pallas
from .smagorinsky import smagorinsky_nut as _smag_pallas
from .wall_model import wall_model_tau as _wm_pallas


# --- dg derivative -----------------------------------------------------------
def dg_derivative3(u: jax.Array, d_matrix: jax.Array, *,
                   impl: str | None = None,
                   block_b: int = 256) -> tuple[jax.Array, ...]:
    if (impl or default_impl()) == "kernel":
        return _dg_pallas(u, d_matrix, block_b=block_b)
    return ref.dg_derivative3(u, d_matrix)


# --- smagorinsky -------------------------------------------------------------
def smagorinsky_nut(grad_v: jax.Array, cs: jax.Array, delta: float, *,
                    impl: str | None = None, block_p: int = 2048) -> jax.Array:
    if (impl or default_impl()) == "kernel":
        return _smag_pallas(grad_v, cs, delta, block_p=block_p)
    return ref.smagorinsky_nut(grad_v, cs, delta)


# --- fused Navier-Stokes RHS -------------------------------------------------
def navier_stokes_rhs_fused(u: jax.Array, cs_nodes: jax.Array,
                            d_matrix: jax.Array, w: jax.Array, *,
                            inv_w_end: tuple[float, float], jac: float,
                            delta: float, mu: float, prandtl: float,
                            prandtl_turb: float, forcing_a0: float,
                            k_tke: float, impl: str | None = None,
                            block_e: int = 1) -> jax.Array:
    """One fused periodic-HIT RHS evaluation (see kernels/rhs.py) — the op
    `cfd/solver.navier_stokes_rhs` dispatches to when kernels are enabled."""
    kw = dict(inv_w_end=inv_w_end, jac=jac, delta=delta, mu=mu,
              prandtl=prandtl, prandtl_turb=prandtl_turb,
              forcing_a0=forcing_a0, k_tke=k_tke)
    if (impl or default_impl()) == "kernel":
        return _rhs_pallas(u, cs_nodes, d_matrix, w, block_e=block_e, **kw)
    return ref.navier_stokes_rhs_fused(u, cs_nodes, d_matrix, w, **kw)


def navier_stokes_rhs_planar(u_pl: jax.Array, cs_pl: jax.Array,
                             d_matrix: jax.Array, w: jax.Array, *, k: int,
                             block_e: int, impl: str | None = None,
                             **kw) -> jax.Array:
    """The fused RHS on planar operands (kernels/rhs.to_planar_batch's
    layout) — the op `cfd/solver.advance_rl_interval` steps its planar RK
    carry with when kernels are enabled.  `kw`: the scalars of
    `navier_stokes_rhs_fused`."""
    if (impl or default_impl()) == "kernel":
        return _rhs_planar_pallas(u_pl, cs_pl, d_matrix, w, k=k,
                                  block_e=block_e, **kw)
    n = d_matrix.shape[0]
    consts = ref.planar_consts(d_matrix, w, n, k, u_pl.shape[-1] // k**3)
    planes = ref.navier_stokes_rhs_planar(u_pl, cs_pl, consts, n=n, k=k,
                                          **kw)
    return jnp.stack(planes).astype(u_pl.dtype)


def wall_rhs_planar(u_pl: jax.Array, scale_pl: jax.Array,
                    d_matrix: jax.Array, w: jax.Array, *,
                    k: tuple[int, int, int], block_e: int,
                    impl: str | None = None, **kw) -> jax.Array:
    """The wall-modelled channel's fused RHS on planar operands
    (kernels/rhs.to_planar_wall_batch's layout) — the op
    `cfd/channel.advance_rl_interval` steps its planar RK carry with when
    kernels are enabled.  `kw`: the scalars of `fused_wall_rhs_planar`."""
    if (impl or default_impl()) == "kernel":
        return _wall_rhs_planar_pallas(u_pl, scale_pl, d_matrix, w, k=k,
                                       block_e=block_e, **kw)
    n = d_matrix.shape[0]
    consts = ref.planar_consts(d_matrix, w, n, k,
                               u_pl.shape[-1] // (k[0] * k[1] * k[2]))
    planes = ref.navier_stokes_rhs_planar(
        u_pl, scale_pl, consts, n=n, k=k, wall_consts=ref.wall_consts(n),
        **kw)
    return jnp.stack(planes).astype(u_pl.dtype)


# --- wall model --------------------------------------------------------------
def wall_model_tau(u_par: jax.Array, rho_w: jax.Array, *, y_m: float,
                   nu: float, kappa: float = 0.41, iters: int = 8,
                   impl: str | None = None, block_p: int = 2048) -> jax.Array:
    """Reichardt-inverted wall stress for a batch of wall-face points."""
    if (impl or default_impl()) == "kernel":
        return _wm_pallas(u_par, rho_w, y_m=y_m, nu=nu, kappa=kappa,
                          iters=iters, block_p=block_p)
    return ref.wall_model_tau(u_par, rho_w, y_m=y_m, nu=nu, kappa=kappa,
                              iters=iters)


# --- flash attention ---------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _fa_with_vjp(q, k, v, causal, window, softcap, scale):
    return _fa_pallas(q, k, v, causal=causal, window=window, softcap=softcap,
                      scale=scale)


def _fa_fwd(q, k, v, causal, window, softcap, scale):
    return _fa_with_vjp(q, k, v, causal, window, softcap, scale), (q, k, v)


def _fa_bwd(causal, window, softcap, scale, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q, k, v: ref.mha_chunked(q, k, v, causal=causal, window=window,
                                        softcap=softcap, scale=scale), q, k, v)
    return vjp(g)


_fa_with_vjp.defvjp(_fa_fwd, _fa_bwd)


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    scale: float | None = None,
    impl: str = "chunked",
    block_k: int = 1024,
    unroll: bool = False,
) -> jax.Array:
    """GQA attention, q (B,Hq,Sq,D), kv (B,Hkv,Skv,D) -> (B,Hq,Sq,D)."""
    if impl == "kernel":
        return _fa_with_vjp(q, k, v, causal, window, softcap, scale)
    if impl == "chunked":
        return ref.mha_chunked(q, k, v, causal=causal, window=window,
                               softcap=softcap, scale=scale,
                               block_k=min(block_k, k.shape[2]),
                               unroll=unroll)
    if impl == "naive":
        return ref.mha(q, k, v, causal=causal, window=window, softcap=softcap,
                       scale=scale)
    raise ValueError(f"unknown attention impl: {impl}")


# --- gated linear recurrence ---------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _ls_with_vjp(q, k, v, w, u, s0, decay_before_read):
    return _ls_pallas(q, k, v, w, u, s0, decay_before_read=decay_before_read)


def _ls_fwd(q, k, v, w, u, s0, decay_before_read):
    return _ls_with_vjp(q, k, v, w, u, s0, decay_before_read), (q, k, v, w, u, s0)


def _ls_bwd(decay_before_read, res, g):
    q, k, v, w, u, s0 = res
    _, vjp = jax.vjp(
        lambda *a: ref.linear_scan_chunked(*a, decay_before_read=decay_before_read),
        q, k, v, w, u, s0)
    return vjp(g)


_ls_with_vjp.defvjp(_ls_fwd, _ls_bwd)


def gated_linear_scan(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,
    u: jax.Array | None = None,
    s0: jax.Array | None = None,
    *,
    decay_before_read: bool = False,
    impl: str = "chunked",
    chunk: int = 64,
    unroll: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """(o, s_final) of the gated linear recurrence (see ref.linear_scan)."""
    if impl == "kernel":
        if u is None or s0 is None:  # custom_vjp wants concrete args
            b, _, dk = q.shape
            u = jnp.zeros((dk,), q.dtype) if u is None else u
            s0 = jnp.zeros((b, dk, v.shape[-1]), jnp.float32) if s0 is None else s0
        return _ls_with_vjp(q, k, v, w, u, s0, decay_before_read)
    if impl == "chunked":
        if unroll:  # cap the unrolled body count (dry-run calibration);
            # inflates only the tiny intra-chunk term (DESIGN.md §5b)
            chunk = max(chunk, q.shape[1] // 16)
        return ref.linear_scan_chunked(q, k, v, w, u, s0,
                                       decay_before_read=decay_before_read,
                                       chunk=chunk, unroll=unroll)
    if impl == "scan":
        return ref.linear_scan(q, k, v, w, u, s0,
                               decay_before_read=decay_before_read)
    raise ValueError(f"unknown linear-scan impl: {impl}")
