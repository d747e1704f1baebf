"""Pallas TPU kernel: chunk-parallel gated linear recurrence.

Serves the attention-free / hybrid cells (rwkv6-1.6b, hymba-1.5b's mamba
heads) and is what makes the 500k-token long-context cells tractable: the
recurrence

    S_t = diag(w_t) S_{t-1} + k_t v_t^T          (state (dk, dv))
    o_t = q_t @ (S_{t-1} + diag(u) k_t v_t^T)    (RWKV6 read)
    o_t = q_t @ S_t                              (GLA/Mamba read)

is restructured into chunks of length C: the O(T) sequential dependence is
carried as one (dk, dv) VMEM-resident state between chunks, while within a
chunk everything is dense MXU work:

    intra: A[t,s] = sum_d q_t[d] k_s[d] exp(cw_t[d] - cw_s[d]),  s <(=) t
    inter: o += (q * exp(cw)) @ S_chunk_start
    state: S' = diag(exp(cw_last)) S + (k * exp(cw_last - cw))^T V

Stability: w in (0, 1], so every exponent above is <= 0 for the masked
(s <= t) entries — the chunk boundary IS the factorization point, no
log-space ratio ever exceeds 1 (this is why the kernel never needs the
fp64 workarounds a naive Q/W, K*W factorization would).

Grid: (B*H, T/C) with the chunk axis sequential; the state is VMEM scratch
and is also emitted as a second output (decode caches it).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from .policy import resolve_interpret


def _kernel(
    q_ref, k_ref, v_ref, w_ref, u_ref, s0_ref,
    o_ref, sfin_ref,
    s_scr,
    *, chunk: int, n_chunks: int, decay_before_read: bool, has_u: bool,
):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        s_scr[...] = s0_ref[0].astype(jnp.float32)

    q = q_ref[0].astype(jnp.float32)  # (C, dk)
    k = k_ref[0].astype(jnp.float32)  # (C, dk)
    v = v_ref[0].astype(jnp.float32)  # (C, dv)
    w = w_ref[0].astype(jnp.float32)  # (C, dk)
    s = s_scr[...]                     # (dk, dv)

    log_w = jnp.log(jnp.maximum(w, 1e-30))
    cw = jnp.cumsum(log_w, axis=0)    # (C, dk): log prod_{s<=t} w_s

    if decay_before_read:
        # GLA: read after decay+write -> decay factor for q_t is exp(cw_t),
        # intra-pair exponent cw_t - cw_s for s <= t (diag: 0).
        q_decay = jnp.exp(cw)
        pair = cw[:, None, :] - cw[None, :, :]          # (C, C, dk)
        mask = jnp.tril(jnp.ones((chunk, chunk), jnp.bool_))
    else:
        # RWKV6: read BEFORE decay/write -> q_t sees exp(cw_{t-1}); strict
        # lower-triangular pairs, diagonal handled by the u-bonus below.
        cw_prev = jnp.concatenate([jnp.zeros_like(cw[:1]), cw[:-1]], axis=0)
        q_decay = jnp.exp(cw_prev)
        pair = cw_prev[:, None, :] - cw[None, :, :]     # (C, C, dk)
        mask = jnp.tril(jnp.ones((chunk, chunk), jnp.bool_), k=-1)

    pair = jnp.where(mask[:, :, None], pair, -jnp.inf)  # exponent <= 0 kept
    a = jnp.einsum("td,sd,tsd->ts", q, k, jnp.exp(pair))
    if not decay_before_read:
        diag = jnp.sum(q * (u_ref[...].astype(jnp.float32) * k if has_u else k),
                       axis=-1)
        a = a + jnp.diag(diag)

    o_intra = jax.lax.dot_general(
        a, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    o_inter = jax.lax.dot_general(
        q * q_decay, s, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    o_ref[0] = (o_intra + o_inter).astype(o_ref.dtype)

    # state update: S' = diag(exp(cw_last)) S + (k * exp(cw_last - cw))^T V
    k_decay = jnp.exp(cw[-1][None, :] - cw)             # (C, dk), <= 1
    s_new = jnp.exp(cw[-1])[:, None] * s + jax.lax.dot_general(
        k * k_decay, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    s_scr[...] = s_new

    @pl.when(ic == n_chunks - 1)
    def _emit_state():
        sfin_ref[0] = s_new.astype(sfin_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("decay_before_read", "chunk", "interpret"),
)
def linear_scan(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,
    u: jax.Array | None = None,
    s0: jax.Array | None = None,
    *,
    decay_before_read: bool = False,
    chunk: int = 64,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Chunked gated linear recurrence; contract = kernels.ref.linear_scan.

    q, k, w: (B, T, dk);  v: (B, T, dv);  u: (dk,) or None;
    s0: (B, dk, dv) or None.  Returns (o: (B, T, dv), s_final: (B, dk, dv)).
    """
    b, t, dk = q.shape
    dv = v.shape[-1]
    chunk = min(chunk, t)
    pad = (-t) % chunk
    if pad:
        # w=1 on padding -> no decay; k=0 -> no state writes; q=0 -> o=0 rows
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
        w = jnp.pad(w, ((0, 0), (0, pad), (0, 0)), constant_values=1.0)
    tp = t + pad
    n_chunks = tp // chunk
    has_u = u is not None
    u_in = u if has_u else jnp.zeros((dk,), q.dtype)
    s0_in = s0 if s0 is not None else jnp.zeros((b, dk, dv), jnp.float32)

    kern = functools.partial(
        _kernel, chunk=chunk, n_chunks=n_chunks,
        decay_before_read=decay_before_read, has_u=has_u,
    )
    o, s_fin = pl.pallas_call(
        kern,
        grid=(b, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, dk), lambda ib, ic: (ib, ic, 0)),
            pl.BlockSpec((1, chunk, dk), lambda ib, ic: (ib, ic, 0)),
            pl.BlockSpec((1, chunk, dv), lambda ib, ic: (ib, ic, 0)),
            pl.BlockSpec((1, chunk, dk), lambda ib, ic: (ib, ic, 0)),
            pl.BlockSpec((dk,), lambda ib, ic: (0,)),
            pl.BlockSpec((1, dk, dv), lambda ib, ic: (ib, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, dv), lambda ib, ic: (ib, ic, 0)),
            pl.BlockSpec((1, dk, dv), lambda ib, ic: (ib, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, tp, dv), q.dtype),
            jax.ShapeDtypeStruct((b, dk, dv), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        interpret=resolve_interpret(interpret),
        name="linear_scan",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(q, k, v, w, u_in, s0_in)
    return (o[:, :t] if pad else o), s_fin
