"""Pallas TPU kernel: blockwise-softmax (flash) attention forward.

Covers every attention variant the assigned architectures need:
  * GQA          (Hq = group * Hkv; the kv block is indexed at bh // group)
  * causal       masking with the decode convention (q occupies the LAST Sq
                 absolute positions of the Skv context)
  * sliding window (h2o-danube / hymba / gemma-2 local layers)
  * logit softcap  (gemma-2: cap * tanh(x / cap))

Grid: (B * Hq, Sq / block_q, Skv / block_k).  The last axis is sequential
on TPU ("arbitrary" dimension semantics): running max / sum / accumulator
live in VMEM scratch and the output block is written once on the final kv
step — the standard online-softmax flash schedule.  VMEM per grid step is
block_q*D (q) + 2*block_k*D (kv) + block_q*(D+2) (scratch): ~0.4 MiB at the
default 512/512 blocks with D=128 — far under budget, so blocks are sized
for MXU alignment (multiples of 128), not VMEM pressure.

Backward: see ops.flash_attention — custom_vjp with a recompute-from-ref
backward (the paper has no training-time attention contribution; fwd is
what serves the prefill/decode cells).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from .policy import resolve_interpret

_NEG_INF = float("-inf")


def _kernel(
    q_ref, k_ref, v_ref, o_ref,
    m_scr, l_scr, acc_scr,
    *, scale: float, causal: bool, window: int | None, softcap: float | None,
    block_q: int, block_k: int, n_kv_blocks: int, sq: int, skv: int,
):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)  # (block_q, D)
    k = k_ref[0].astype(jnp.float32)  # (block_k, D)
    v = v_ref[0].astype(jnp.float32)

    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)

    # absolute positions: q block rows / k block cols
    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 0) \
        + (skv - sq)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    mask = k_pos < skv  # guard kv padding
    mask &= q_pos < skv  # guard q padding (rows beyond sq)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    logits = jnp.where(mask, logits, _NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1))
    # fully-masked-so-far rows keep m = -inf; guard the rescale factor
    alpha = jnp.exp(jnp.where(jnp.isneginf(m_prev), 0.0, m_prev - m_new))
    safe_m = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.exp(jnp.where(mask, logits - safe_m[:, None], _NEG_INF))
    p = jnp.where(mask, p, 0.0)
    m_scr[...] = m_new
    l_scr[...] = alpha * l_prev + jnp.sum(p, axis=-1)
    acc_scr[...] = alpha[:, None] * acc_scr[...] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(ik == n_kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "scale", "block_q",
                     "block_k", "interpret"),
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    scale: float | None = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Flash attention forward; contract identical to kernels.ref.mha.

    q: (B, Hq, Sq, D);  k, v: (B, Hkv, Skv, D).  Returns (B, Hq, Sq, D).
    """
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    assert hq % hkv == 0, "GQA requires Hq % Hkv == 0"
    group = hq // hkv
    scale = (d ** -0.5) if scale is None else scale

    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    pad_q = (-sq) % block_q
    pad_k = (-skv) % block_k
    qf = q.reshape(b * hq, sq, d)
    kf = k.reshape(b * hkv, skv, d)
    vf = v.reshape(b * hkv, skv, d)
    if pad_q:
        qf = jnp.pad(qf, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kf = jnp.pad(kf, ((0, 0), (0, pad_k), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad_k), (0, 0)))
    sq_p, skv_p = sq + pad_q, skv + pad_k
    n_kv_blocks = skv_p // block_k
    grid = (b * hq, sq_p // block_q, n_kv_blocks)

    kern = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k, n_kv_blocks=n_kv_blocks,
        sq=sq, skv=skv,
    )
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, iq, ik, grp=group: (bh // grp, ik, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, iq, ik, grp=group: (bh // grp, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq_p, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
        name="flash_attention_fwd",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(qf, kf, vf)
    out = out[:, :sq] if pad_q else out
    return out.reshape(b, hq, sq, d)
