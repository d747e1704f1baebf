"""GQA attention for the assigned LM architectures.

Covers: grouped-query attention, RoPE, sliding windows (ring-buffer KV
caches), gemma-2 logit softcapping, optional QKV biases.  Three entry
points matching the three cell kinds:

  full_attention     train_4k        — causal self-attention, no cache
  prefill_attention  prefill_32k     — causal self-attention + cache build
  decode_attention   decode/long     — one token against a KV cache

Decode against a sequence-sharded cache supports two combine strategies:

  "allgather"  (baseline) let XLA SPMD all-gather the KV shard — what a
               naive pjit of the math produces; moves O(S*D*Hkv) per step.
  "flash"      flash-decoding: shard_map over the cache's mesh axis, each
               shard attends to its KV slice and emits (out, logsumexp);
               a tiny psum-combine merges the partial softmaxes — moves
               O(Hq*D) per step.  The §Perf hillclimb quantifies the gap.

Compute dtype follows the inputs; softmax statistics are always f32.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import nn
from ..kernels import ops as kops
from ..parallel import sharding
from .config import ArchConfig


# --- RoPE --------------------------------------------------------------------
def rope_table(positions: jax.Array, head_dim: int, theta: float) -> tuple[jax.Array, jax.Array]:
    """(cos, sin) tables for `positions` (any shape) -> (..., head_dim/2)."""
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate pairs (x[..., :h], x[..., h:]) — the neox/llama convention.

    x: (B, H, S, D); cos/sin: (S, D/2) or broadcastable (B, 1, S, D/2).
    """
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    while cos.ndim < x1.ndim:  # (S, h) -> (1, 1, S, h)
        cos, sin = cos[None], sin[None]
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([x1f * cos - x2f * sin, x1f * sin + x2f * cos], axis=-1)
    return out.astype(x.dtype)


# --- parameters ---------------------------------------------------------------
def init(key: jax.Array, cfg: ArchConfig) -> dict:
    """One attention block's parameters."""
    d, hd = cfg.d_model, cfg.hd
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "wq": nn.dense_init(kq, d, cfg.n_heads * hd, bias=cfg.attn_bias),
        "wk": nn.dense_init(kk, d, cfg.kv_heads * hd, bias=cfg.attn_bias),
        "wv": nn.dense_init(kv, d, cfg.kv_heads * hd, bias=cfg.attn_bias),
        "wo": nn.dense_init(ko, cfg.n_heads * hd, d, bias=cfg.attn_bias),
    }
    return p


def axes(cfg: ArchConfig) -> dict:
    """Logical axes mirroring `init` (see parallel.sharding.param_specs)."""
    def with_bias(ax):
        return {"w": ax, "b": (ax[-1],)} if cfg.attn_bias else {"w": ax}

    return {
        "wq": with_bias(("embed", "heads")),
        "wk": with_bias(("embed", "kv_heads")),
        "wv": with_bias(("embed", "kv_heads")),
        "wo": with_bias(("heads", "embed")),
    }


# --- cache --------------------------------------------------------------------
def init_cache(cfg: ArchConfig, batch: int, max_len: int, *, window: int | None,
               dtype=jnp.bfloat16) -> dict:
    """Empty KV cache for one layer.  Sliding-window layers get a ring
    buffer bounded by the window; global layers a full-length buffer."""
    length = min(window, max_len) if window else max_len
    shape = (batch, cfg.kv_heads, length, cfg.hd)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "pos": jnp.zeros((), jnp.int32),  # absolute position of the next write
    }


def cache_axes() -> dict:
    return {
        "k": ("batch", "kv_heads", "kv_seq", None),
        "v": ("batch", "kv_heads", "kv_seq", None),
        "pos": None,
    }


def _qkv(p: dict, cfg: ArchConfig, x: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """x (B, S, D) -> q (B, Hq, S, hd), k/v (B, Hkv, S, hd)."""
    b, s, _ = x.shape
    hd = cfg.hd
    q = nn.dense(p["wq"], x, dtype=x.dtype).reshape(b, s, cfg.n_heads, hd)
    k = nn.dense(p["wk"], x, dtype=x.dtype).reshape(b, s, cfg.kv_heads, hd)
    v = nn.dense(p["wv"], x, dtype=x.dtype).reshape(b, s, cfg.kv_heads, hd)
    q, k, v = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
    q = sharding.constrain(q, "batch", "heads", None, None)
    k = sharding.constrain(k, "batch", "kv_heads", None, None)
    v = sharding.constrain(v, "batch", "kv_heads", None, None)
    return q, k, v


def _out(p: dict, cfg: ArchConfig, o: jax.Array) -> jax.Array:
    """o (B, Hq, S, hd) -> (B, S, D)."""
    b, _, s, _ = o.shape
    o = jnp.swapaxes(o, 1, 2).reshape(b, s, cfg.n_heads * cfg.hd)
    return nn.dense(p["wo"], o, dtype=o.dtype)


# --- train / prefill -----------------------------------------------------------
def full_attention(
    p: dict, cfg: ArchConfig, x: jax.Array, *, window: int | None,
    positions: jax.Array | None = None,
) -> jax.Array:
    """Causal self-attention over the whole sequence (training path)."""
    b, s, _ = x.shape
    q, k, v = _qkv(p, cfg, x)
    if cfg.rope:
        pos = jnp.arange(s) if positions is None else positions
        cos, sin = rope_table(pos, cfg.hd, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    o = kops.attention(
        q, k, v, causal=True, window=window,
        softcap=cfg.attn_softcap or None, scale=cfg.attn_scale or None,
        impl=cfg.attn_impl, block_k=cfg.attn_block_k,
        unroll=cfg.unroll_scans,
    )
    return _out(p, cfg, o)


def prefill_attention(
    p: dict, cfg: ArchConfig, x: jax.Array, cache: dict, *, window: int | None,
) -> tuple[jax.Array, dict]:
    """Causal self-attention + cache population (prefill path).

    Assumes an empty cache (pos == 0) and s <= cache length for global
    layers; sliding-window layers keep only the trailing `window` keys.
    """
    b, s, _ = x.shape
    q, k, v = _qkv(p, cfg, x)
    if cfg.rope:
        cos, sin = rope_table(jnp.arange(s), cfg.hd, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    o = kops.attention(
        q, k, v, causal=True, window=window,
        softcap=cfg.attn_softcap or None, scale=cfg.attn_scale or None,
        impl=cfg.attn_impl, block_k=cfg.attn_block_k,
        unroll=cfg.unroll_scans,
    )
    length = cache["k"].shape[2]
    if length >= s:  # global layer: write [0, s)
        k_new = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0))
        v_new = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0))
    else:  # ring buffer: keep the last `length` positions, slot = pos % length
        tail_k = k[:, :, s - length:, :].astype(cache["k"].dtype)
        tail_v = v[:, :, s - length:, :].astype(cache["v"].dtype)
        slots = (jnp.arange(length) + (s - length)) % length
        k_new = jnp.zeros_like(cache["k"]).at[:, :, slots, :].set(tail_k)
        v_new = jnp.zeros_like(cache["v"]).at[:, :, slots, :].set(tail_v)
    new_cache = {"k": k_new, "v": v_new, "pos": jnp.asarray(s, jnp.int32)}
    return _out(p, cfg, o), new_cache


# --- decode ---------------------------------------------------------------------
def _partial_softmax_attn(q, k, v, mask, softcap, scale):
    """Attention over a KV slice returning partial-softmax statistics.

    q: (B, Hq, 1, D); k/v: (B, Hkv, L, D); mask: (B, 1, 1, L) or (1,1,1,L).
    Returns (acc, m, l): acc (B, Hq, 1, D) = sum exp(logits - m_safe) * v,
    m (B, Hq, 1) the row max (-inf when fully masked), l (B, Hq, 1) the
    exp-sum.  out = acc / l locally; cross-shard combining rescales by
    exp(m - m_max) first (flash-decoding).
    """
    group = q.shape[1] // k.shape[1]
    kg = jnp.repeat(k, group, axis=1).astype(jnp.float32)
    vg = jnp.repeat(v, group, axis=1).astype(jnp.float32)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kg) * scale
    if softcap:
        logits = softcap * jnp.tanh(logits / softcap)
    logits = jnp.where(mask, logits, -jnp.inf)
    m = jnp.max(logits, axis=-1)  # (B, Hq, 1)
    m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
    p = jnp.exp(logits - m_safe[..., None])
    p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhqk,bhkd->bhqd", p, vg)
    return acc, m, l


def decode_attention(
    p: dict, cfg: ArchConfig, x: jax.Array, cache: dict, *, window: int | None,
    combine: str = "allgather",
) -> tuple[jax.Array, dict]:
    """One-token attention against the cache.  x: (B, 1, D)."""
    b = x.shape[0]
    q, k, v = _qkv(p, cfg, x)  # (B, H*, 1, hd)
    pos = cache["pos"]  # absolute position of this token
    if cfg.rope:
        cos, sin = rope_table(pos[None], cfg.hd, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

    length = cache["k"].shape[2]
    slot = (pos % length) if window else jnp.minimum(pos, length - 1)
    scale = cfg.attn_scale or cfg.hd ** -0.5

    if combine == "flash":
        rules = sharding.current_rules()
        axis = rules.mesh_axes("kv_seq") if rules else None
        if rules is not None and rules.mesh is not None and axis is not None \
                and length % rules.mesh.shape[axis] == 0:
            o, k_cache, v_cache = _flash_decode(
                q, cache["k"], cache["v"], k, v, pos, slot,
                bool(window), cfg.attn_softcap or 0.0, scale, rules, axis)
            new_cache = {"k": k_cache, "v": v_cache, "pos": pos + 1}
            return _out(p, cfg, o.astype(x.dtype)), new_cache
        # fall through to the dense path when no mesh/axis applies

    k_cache = jax.lax.dynamic_update_slice(
        cache["k"], k.astype(cache["k"].dtype), (0, 0, slot, 0))
    v_cache = jax.lax.dynamic_update_slice(
        cache["v"], v.astype(cache["v"].dtype), (0, 0, slot, 0))
    new_cache = {"k": k_cache, "v": v_cache, "pos": pos + 1}

    # Valid-slot mask.  Ring buffer: slot s holds absolute position
    # pos - ((pos - s) mod L) <= pos, all within (pos-L, pos] -> valid iff
    # written (abs position <= pos, automatically true once warm; cold slots
    # s > pos are excluded).  Global buffer: slots [0, pos] valid.
    slots = jnp.arange(length)
    if window:
        abs_pos = pos - jnp.mod(pos - slots, length)
        mask = abs_pos >= 0
    else:
        mask = slots <= pos
    mask = mask[None, None, None, :]

    k_cache = sharding.constrain(k_cache, "batch", "kv_heads", "kv_seq", None)
    v_cache = sharding.constrain(v_cache, "batch", "kv_heads", "kv_seq", None)
    acc, _, l = _partial_softmax_attn(q, k_cache, v_cache, mask,
                                      cfg.attn_softcap or 0.0, scale)
    o = acc / jnp.maximum(l, 1e-30)[..., None]
    return _out(p, cfg, o.astype(x.dtype)), new_cache


def _flash_decode(q, k_cache, v_cache, k_new, v_new, pos, slot, is_window,
                  softcap, scale, rules, axis):
    """Flash-decoding with a SHARD-LOCAL cache update.

    Two things must stay local to the sequence shard or XLA SPMD gathers the
    whole cache every step (measured: ~86 GB/step on command-r decode_32k):
      1. the single-token dynamic_update_slice (a dynamic index into a
         sharded dim) — done here with shard-local slot arithmetic;
      2. the softmax over the sharded KV axis — partial (acc, max, sum)
         statistics merge with an O(B*Hq*D) psum:
         out = sum_i acc_i·exp(m_i-m_max) / sum_i l_i·exp(m_i-m_max).
    """
    mesh = rules.mesh
    length = k_cache.shape[2]
    n_shards = mesh.shape[axis]
    local_len = length // n_shards
    batch_axes = rules.mesh_axes("batch")
    if isinstance(batch_axes, str):
        batch_axes = (batch_axes,)
    b_ax = tuple(a for a in (batch_axes or ()) if a in mesh.shape
                 and q.shape[0] % mesh.shape[a] == 0) or None
    b_spec = b_ax if b_ax is None else (b_ax if len(b_ax) > 1 else b_ax[0])

    def shard_fn(q_s, kc, vc, kn, vn, pos_s, slot_s):
        idx = jax.lax.axis_index(axis)
        local_slot = slot_s - idx * local_len
        in_range = (local_slot >= 0) & (local_slot < local_len)
        safe = jnp.clip(local_slot, 0, local_len - 1)
        kc_upd = jax.lax.dynamic_update_slice(
            kc, kn.astype(kc.dtype), (0, 0, safe, 0))
        vc_upd = jax.lax.dynamic_update_slice(
            vc, vn.astype(vc.dtype), (0, 0, safe, 0))
        kc = jnp.where(in_range, kc_upd, kc)
        vc = jnp.where(in_range, vc_upd, vc)
        abs_slots = idx * local_len + jnp.arange(local_len)
        if is_window:
            mask = (pos_s - jnp.mod(pos_s - abs_slots, length)) >= 0
        else:
            mask = abs_slots <= pos_s
        acc, m, l = _partial_softmax_attn(q_s, kc, vc,
                                          mask[None, None, None, :],
                                          softcap, scale)
        m_max = jax.lax.pmax(m, axis)  # decode always has >= 1 valid key
        w = jnp.exp(m - m_max)         # 0 on fully-masked shards (m = -inf)
        num = jax.lax.psum(acc * w[..., None], axis)
        den = jax.lax.psum(l * w, axis)
        return num / jnp.maximum(den, 1e-30)[..., None], kc, vc

    spec_kv = P(b_spec, None, axis, None)
    spec_tok = P(b_spec, None, None, None)
    return jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(spec_tok, spec_kv, spec_kv, spec_tok, spec_tok, P(), P()),
        out_specs=(spec_tok, spec_kv, spec_kv),
        check_vma=False,
    )(q, k_cache, v_cache, k_new, v_new, pos, slot)
