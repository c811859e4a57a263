"""Layout helpers shared by the cache-attention Pallas kernels.

``decode_attention.py`` (Sq=1) and ``prefill_attention.py`` (Sq>=1, cache
continuation) read the same KV cache through ONE kernel, which walks a
PAGED arena: a contiguous (B, S, Hkv, hd) cache is handed to it as an arena
of ``bk``-position pages with an identity page table (``as_pages``), so the
contiguous and paged wrappers differ only in where the table comes from.

This module also owns the PAGED layout's logical<->physical index math,
shared by all three backends (DESIGN.md §12). A paged KV arena drops the
slot axis: leaves are (n_pages, page_size, Hkv, hd) and each slot carries a
page table row (max_pages,) of physical page ids, so logical position ``p``
of a slot lives at ``arena[table[p // page_size], p % page_size]``. The
three consumers:

  * ``gather_pages``      — the xla/ref read path: materialize the visible
    window as a contiguous (B, n_blk*page_size, ...) view, then reuse the
    contiguous einsum/kernel verbatim (gathered content == the contiguous
    prefix, so windowed numerics are bit-identical by construction);
  * ``scatter_pages``     — the write path (``models.attention``): flat
    per-element scatter through the same table;
  * the Pallas kernel skips the gather entirely — the KV-block grid axis
    walks the table via scalar-prefetch BlockSpec index maps with the block
    size pinned to ``page_size``, so block j's physical index IS
    ``table[b, j]``.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def as_pages(k: jax.Array, v: jax.Array, k_s: Optional[jax.Array],
             v_s: Optional[jax.Array], bk: int) -> Tuple:
    """View a contiguous (B, S, Hkv, hd) cache as a paged arena of
    ``bk``-position pages: (B*n, bk, Hkv, hd) leaves (scales (B*n, bk,
    Hkv)) plus the identity (B, n) page table. The sequence axis is
    zero-padded to a ``bk`` multiple first; the padded tail sits beyond
    every real row's causal limit, so the kernel's position mask
    neutralizes it exactly (exp(-inf) = +0.0 contributions). Returns
    (k, v, k_s, v_s, pages)."""
    b, s_len = k.shape[:2]
    pk = (-s_len) % bk
    n = (s_len + pk) // bk

    def page(t):
        if t is None:
            return None
        if pk:
            t = jnp.pad(t, ((0, 0), (0, pk)) + ((0, 0),) * (t.ndim - 2))
        return t.reshape((b * n, bk) + t.shape[2:])
    pages = jnp.arange(b * n, dtype=jnp.int32).reshape(b, n)
    return page(k), page(v), page(k_s), page(v_s), pages


def page_scales(s: jax.Array) -> jax.Array:
    """(n_pages, page_size, Hkv) f32 dequant scales -> (n_pages, Hkv,
    page_size): a page's scales for all heads form one (Hkv, page_size)
    block — both dims whole, as the TPU tiling rule requires — and head
    ``h``'s row broadcasts over a score tile's rows in the kernel."""
    return jnp.transpose(s, (0, 2, 1))


# ------------------------------------------------------------------- paged
def to_store(x: jax.Array, store_dtype) -> jax.Array:
    """Value -> arena storage dtype. A uint16 arena holds raw bfloat16 bit
    patterns (see ``init_kv_cache(paged=True)``): XLA CPU has no native
    bf16 scatter — the float-normalization pass rewrites it through f32
    converts, which materializes a full copy of the arena on EVERY cache
    write (the copy scales with ``total_pages``, not with the tokens
    written). Scatter on uint16 is pure data movement and stays in place
    under donation, so paged arenas store bf16 as raw 16-bit words and
    bitcast at the (small) read/write boundaries — bit patterns are
    untouched, so paged numerics stay bit-identical."""
    if store_dtype == jnp.uint16 and x.dtype != jnp.uint16:
        return jax.lax.bitcast_convert_type(x.astype(jnp.bfloat16),
                                            jnp.uint16)
    return x.astype(store_dtype)


def from_store(x: jax.Array) -> jax.Array:
    """Arena storage -> compute value: uint16 bitcasts back to bfloat16,
    every other dtype (bf16 test fixtures, int8 quantized KV) passes
    through untouched."""
    if x.dtype == jnp.uint16:
        return jax.lax.bitcast_convert_type(x, jnp.bfloat16)
    return x


def page_count(tokens: int, page_size: int) -> int:
    """Pages needed to hold ``tokens`` logical positions (host-side)."""
    return -(-tokens // page_size)


def window_pages(pages: jax.Array, page_size: int,
                 window: Optional[int]) -> jax.Array:
    """Slice a (B, max_pages) table to the (B, n_blk) prefix covering the
    static visible ``window`` (None = every page). The gathered window may
    round up past ``window`` to a page multiple — the extra tail positions
    sit beyond every causal limit and mask to exact zeros, so a page-rounded
    window is bit-identical to the exact one."""
    n_blk = (pages.shape[1] if window is None
             else min(pages.shape[1], page_count(window, page_size)))
    return jax.lax.slice_in_dim(pages, 0, max(n_blk, 1), axis=1)


def gather_pages(leaf: jax.Array, pages: jax.Array) -> jax.Array:
    """Materialize a paged arena's visible window as a contiguous view.

    leaf: (n_pages, page_size, ...) arena; pages: (B, n_blk) int32 physical
    page ids (a ``window_pages`` prefix). Returns (B, n_blk*page_size, ...)
    — exactly what the contiguous layout's first ``n_blk*page_size``
    positions would hold, with unallocated table entries (physical page 0,
    the trash page) contributing garbage only at positions beyond every
    consumer's causal limit."""
    b, n_blk = pages.shape
    g = jnp.take(leaf, pages, axis=0)          # (B, n_blk, page_size, ...)
    return from_store(g.reshape((b, n_blk * leaf.shape[1])
                                + leaf.shape[2:]))


def paged_element_index(pages: jax.Array, pos: jax.Array, sn: int,
                        page_size: int) -> jax.Array:
    """Flat physical element indices for logical positions pos..pos+sn-1.

    pages: (B, max_pages) int32; pos: (B,) int32. Returns (B, sn) int32
    into a ``(n_pages*page_size, ...)``-flattened arena. A negative logical
    position (an inactive row's clamped speculative healing chunk) floors
    into block -1, which the gather clamps to the row's first table entry —
    the engine points inactive rows' tables at the trash page, so the stray
    write lands there."""
    p = pos[:, None] + jnp.arange(sn, dtype=jnp.int32)[None, :]
    blk = jnp.clip(p // page_size, 0, pages.shape[1] - 1)
    phys = jnp.take_along_axis(pages, blk, axis=1)
    return phys * page_size + p % page_size


def scatter_pages(leaf: jax.Array, upd: jax.Array, pages: jax.Array,
                  pos: jax.Array) -> jax.Array:
    """Write (B, sn, ...) ``upd`` at logical positions pos..pos+sn-1 through
    the page table. leaf: (n_pages, page_size, ...) arena (shared across
    rows — distinct slots never map the same writable page, so row scatters
    cannot collide outside the trash page)."""
    n_pages, ps = leaf.shape[:2]
    b, sn = upd.shape[:2]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    idx = paged_element_index(pages, pos, sn, ps).reshape(-1)
    upd = to_store(upd.reshape((b * sn,) + upd.shape[2:]), leaf.dtype)
    flat = leaf.reshape((n_pages * ps,) + leaf.shape[2:])
    return flat.at[idx].set(upd).reshape(leaf.shape)
