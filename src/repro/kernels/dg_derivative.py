"""Pallas TPU kernel: fused three-direction DGSEM derivative.

The DGSEM volume term applies the (n x n) Lagrange derivative matrix D along
each of the three intra-element node axes of every element — three tiny
contractions over a huge element batch (the solver's dominant FLOP term,
paper Sec. 3.2 / FLEXI).

Arithmetic intensity per point is low (3n MACs vs 4 channel floats moved),
so the win on TPU is HBM traffic, not MXU utilization: computing all three
directions in ONE pass over u reads u once instead of three times.

Layout: u (B, n, n, n, C) is transposed to the planar (n^3, B C) view of
kernels/ref.py — node rows by (element, channel) lanes — so both minor dims
of a block are tile-dense; the natural layout ends in (n, C), which Mosaic
cannot reshape into matmul operands.  Each derivative is then a sum over
row offsets of D-coefficient columns times row-rotated copies of the block
(`ref.planar_deriv`), all on the VPU/XLU.  Each grid step processes a block
of lanes (`block_b` elements' worth, rounded up to 128 lanes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref
from .policy import resolve_interpret, scoped_vmem_limit


def _kernel(u_ref, coef_ref, du0_ref, du1_ref, du2_ref, *, n):
    x = u_ref[...].astype(jnp.float32)
    for d, out_ref in enumerate((du0_ref, du1_ref, du2_ref)):
        du = ref.planar_deriv(x, coef_ref, n, d, ref.kernel_roll)
        out_ref[...] = du.astype(out_ref.dtype)


def _vmem_limit_bytes(n: int, lanes: int) -> int | None:
    """Double-buffered in + 3 out blocks, 8 planes of intermediates and
    the resident (P, 1) coefficient columns, padded to the (8, 128) tile."""
    p = -(-n**3 // 8) * 8
    plane = p * -(-lanes // 128) * 128 * 4
    return scoped_vmem_limit(16 * plane + 3 * (2 * n - 1) * p * 128 * 4)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def dg_derivative3(
    u: jax.Array,
    d_matrix: jax.Array,
    *,
    block_b: int = 128,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused (du/dxi_0, du/dxi_1, du/dxi_2) for an element batch.

    u: (B, n, n, n, C);  d_matrix: (n, n).  Matches kernels.ref.dg_derivative3.
    """
    b, n, _, _, c = u.shape
    p, lanes = n**3, b * c
    x = jnp.transpose(u, (1, 2, 3, 0, 4)).reshape(p, lanes)
    block = -(-block_b * c // 128) * 128
    if lanes <= block:
        block, pad = lanes, 0
    else:
        pad = (-lanes) % block
        x = jnp.pad(x, ((0, 0), (0, pad)))
    spec = pl.BlockSpec((p, block), lambda i: (0, i))
    out_shape = jax.ShapeDtypeStruct((p, lanes + pad), u.dtype)
    outs = pl.pallas_call(
        functools.partial(_kernel, n=n),
        grid=((lanes + pad) // block,),
        in_specs=[spec, pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=[spec, spec, spec],
        out_shape=[out_shape] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_vmem_limit_bytes(n, block)),
        interpret=resolve_interpret(interpret),
        name="dg_derivative3",
    )(x, ref.deriv_coef(d_matrix, n))
    return tuple(
        jnp.transpose(o[:, :lanes].reshape(n, n, n, b, c), (3, 0, 1, 2, 4))
        for o in outs)
