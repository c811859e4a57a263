"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth)."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def quantize_ref(x: jax.Array, axis: int = -1) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-channel INT8: returns (q, scale) with x ≈ q * scale.

    ``scale`` has ``axis`` reduced away (one scale per remaining index)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, jnp.squeeze(scale, axis=axis)


def int8_matmul_ref(x: jax.Array, w_q: jax.Array, w_scale: jax.Array,
                    x_scale: Optional[jax.Array] = None) -> jax.Array:
    """W8A8 matmul oracle. x: (M, K) float (dynamically quantized if no
    x_scale) or int8; w_q: (K, N) int8; w_scale: (N,)."""
    if x.dtype != jnp.int8:
        x_q, x_scale = quantize_ref(x, axis=-1)
    else:
        x_q = x
        assert x_scale is not None
    acc = jax.lax.dot_general(
        x_q, w_q, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32).astype(jnp.float32)
    return (acc * x_scale[:, None] * w_scale[None, :]).astype(jnp.bfloat16)


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True, q_offset: int = 0) -> jax.Array:
    """Naive (materialized-scores) MHA oracle. q,k,v: (B, S, H, hd).

    Causality is ABSOLUTE-position: query i sits at position
    ``q_offset + i`` and sees ``kv_pos <= q_offset + i`` — the single
    Sq<Skv convention shared with ``models.attention.flash_attention`` and
    the ``start`` argument of ``cached_attention_ref``
    (``flash_attention_ref(q_offset=o) == cached_attention_ref(start=o)``
    up to dtype staging). The default ``q_offset=0`` makes queries the
    FIRST Sq positions. (This replaces an older ``tril(k=skv-sq)`` mask
    that silently pinned queries to the LAST Sq positions — the opposite of
    what the model's flash path assumed, a drift the prefill kernel would
    otherwise have validated against.)"""
    b, sq, h, hd = q.shape
    skv = k.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * hd ** -0.5
    if causal:
        q_pos = q_offset + jnp.arange(sq)
        mask = jnp.arange(skv)[None, :] <= q_pos[:, None]      # (Sq, Skv)
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


def cached_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                         k_s: Optional[jax.Array] = None,
                         v_s: Optional[jax.Array] = None,
                         start: jax.Array = None) -> jax.Array:
    """Masked-einsum GQA attention over a slotted KV window — the canonical
    XLA-fallback numerics shared by cache-continuation prefill and the ``xla``
    backend's ``decode_attention`` (bit-identity between the two is what keeps
    engine output token-identical to serial decode).

    q: (B, Sq, Hq, hd) queries at absolute positions ``start..start+Sq-1``;
    k, v: (B, W, Hkv, hd) float, or int8 with ``k_s``/``v_s`` (B, W, Hkv) f32
    scales — for the INT8 cache the per-(pos, head) dequant is fused into the
    score/probability matrices (size B·H·Sq·W) instead of the cache (size
    B·H·W·hd): the cache itself is only ever read as int8. ``start``: (B,)
    int32. W is the visible window: callers guarantee ``W >= start+Sq`` for
    every row whose output is consumed. Returns (B, Sq, Hq, hd) bf16.
    """
    b, sq, hq, hd = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    qg = (q.reshape(b, sq, hkv, g, hd).astype(jnp.float32) * hd ** -0.5
          ).astype(jnp.bfloat16)
    s = jnp.einsum("bqhgd,bchd->bqhgc", qg, k.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)
    if k_s is not None:
        s = s * jnp.transpose(k_s, (0, 2, 1))[:, None, :, None, :]
    limit = start[:, None] + jnp.arange(sq)[None, :]          # (B, Sq)
    mask = jnp.arange(skv)[None, None, :] <= limit[..., None]  # (B, Sq, W)
    s = jnp.where(mask[:, :, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if v_s is not None:
        p = p * jnp.transpose(v_s, (0, 2, 1))[:, None, :, None, :]
    out = jnp.einsum("bqhgc,bchd->bqhgd", p.astype(jnp.bfloat16),
                     v.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    return out.reshape(b, sq, hq, hd).astype(jnp.bfloat16)


def decode_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                         k_s: Optional[jax.Array] = None,
                         v_s: Optional[jax.Array] = None,
                         start: jax.Array = None) -> jax.Array:
    """Single-query decode attention (the ``xla`` backend primitive).

    q: (B, Hq, hd); k/v/k_s/v_s/start as ``cached_attention_ref``. Defined as
    exactly the Sq=1 slice of the prefill einsum so decode and chunked
    prefill share one set of numerics bit-for-bit."""
    return cached_attention_ref(q[:, None], k, v, k_s, v_s, start)[:, 0]


# ------------------------------------------------------------------- paged
def _gathered_window(k, v, k_s, v_s, pages):
    """Materialize a paged arena window as contiguous (B, W, ...) views —
    the xla paged read path IS gather + the contiguous einsum, which is what
    pins paged numerics bit-identical to the contiguous layout."""
    from repro.kernels.kv_layout import gather_page_scales, gather_pages
    s = lambda t: (None if t is None
                   else gather_page_scales(t, pages, k.shape[1]))
    return gather_pages(k, pages), gather_pages(v, pages), s(k_s), s(v_s)


def paged_prefill_attention_ref(q, k, v, k_s, v_s, start, pages):
    """q: (B, Sq, Hq, hd); k/v: (n_pages, page_size, Hkv, hd) arenas (int8
    with (n_pages, Hkv*page_size) scales when quantized); pages: (B, n_blk)
    int32 window prefix of each row's page table; start as the contiguous
    primitive."""
    return cached_attention_ref(q, *_gathered_window(k, v, k_s, v_s, pages),
                                start=start)


def paged_decode_attention_ref(q, k, v, k_s, v_s, start, pages):
    """Sq=1 slice of ``paged_prefill_attention_ref`` (q: (B, Hq, hd))."""
    return decode_attention_ref(q, *_gathered_window(k, v, k_s, v_s, pages),
                                start=start)


