"""Pallas TPU decode attention: one query token per slot against the cache.

Decode is the Sq=1 case of the cache-continuation kernel in
``prefill_attention.py`` (split-KV flash decoding over the KV-block grid
axis): each batch row carries its own visible limit ``start`` (the absolute
position of the query), and every KV block strictly beyond that limit is
skipped via ``pl.when`` and never fetched, so a slot that is 40 tokens into
a 4096-slot cache issues work for its first few blocks only. That block
skip is what makes decode cost track *actual* sequence length instead of
cache capacity. The INT8 KV path fuses the dequant into the score and
probability tiles (see the kernel module).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels.prefill_attention import (paged_prefill_attention_pallas,
                                             prefill_attention_pallas)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def decode_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                            k_s: Optional[jax.Array] = None,
                            v_s: Optional[jax.Array] = None,
                            start: jax.Array = None, *, bk: int = 16,
                            interpret: bool = False) -> jax.Array:
    """q: (B, Hq, hd); k/v: (B, S, Hkv, hd) float or int8 (then k_s/v_s
    (B, S, Hkv) f32 scales); start: (B,) int32 per-slot query positions.
    Returns (B, Hq, hd) bf16."""
    return prefill_attention_pallas(q[:, None], k, v, k_s, v_s, start, bq=1,
                                    bk=bk, interpret=interpret)[:, 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                                  k_s: Optional[jax.Array] = None,
                                  v_s: Optional[jax.Array] = None,
                                  start: jax.Array = None,
                                  pages: jax.Array = None, *,
                                  interpret: bool = False) -> jax.Array:
    """Page-table-indirect decode: q (B, Hq, hd) vs a PAGED arena k/v
    (n_pages, page_size, Hkv, hd) (scales (n_pages, page_size, Hkv)),
    start (B,) int32, pages (B, n_blk) int32 — see
    ``paged_prefill_attention_pallas``. Returns (B, Hq, hd) bf16."""
    return paged_prefill_attention_pallas(q[:, None], k, v, k_s, v_s, start,
                                          pages, bq=1,
                                          interpret=interpret)[:, 0]
