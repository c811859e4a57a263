"""Pallas TPU kernel: fused chunked-prefill (cache-continuation) attention.

The one cache-attention kernel: a chunk of Sq query tokens per slot attends
a PAGED KV arena (n_pages, page_size, Hkv, hd) through a per-slot page table
— a contiguous (B, W, Hkv, hd) cache is handed over as an arena of ``bk``
pages with an identity table (``kv_layout.as_pages``), and decode is the
Sq=1 chunk (``decode_attention.py``). Grid (B, Sq/bq, n_blk) with the
KV-block axis innermost; the online-softmax accumulators (m, l, acc) live
in VMEM scratch across the KV loop per query tile, so no (B, Sq, Hkv, G, W)
score tensor is ever materialized.

Block layout (DESIGN.md §Backend-registry): every block keeps the arrays'
last two dims whole, as the TPU tiling rule requires of dims that are not
(8, 128) multiples — a q/out tile is (1, bq, Hq, hd), a KV page (1, ps, Hkv,
hd), and for a page's scales the (``SCALE_ROWS``, Hkv*ps) tile of rows
that holds its row, whose lanes [h*ps, (h+1)*ps) are head ``h``'s scales
along a score tile's columns. The kernel walks the heads itself: each KV
head's (ps, hd) slice is read once and serves its G = Hq/Hkv query heads
(GQA), each query head's (bq, hd) rows run their own online softmax.
``start`` and the page table are scalar-prefetch (SMEM) operands; the table
drives the KV index maps, which also clamp the block index to the tile's
last visible page so pages past the causal limit are never DMA'd.

Causality is *absolute*, per slot: each batch row carries ``start`` (the
chunk's first absolute position) and query i of the chunk sees exactly cache
positions <= start + i. Because the limit depends only on the query's
absolute position — never on the chunk boundaries, the query-tile size, or
the window bucket — chunk N of a prompt attends chunks 0..N with the same
per-row arithmetic as a whole-prompt prefill: KV blocks fully beyond a row's
limit contribute exact no-ops (p == +0.0, corr == 1.0) when visited and are
skipped entirely via ``pl.when`` when the whole tile is past them, so chunked
and whole-prompt prefill are *bit-consistent* row for row. The block size is
part of the arithmetic (the online softmax folds one block at a time): a
contiguous cache read with ``bk`` equal to a paged arena's page size gives
the paged result bit for bit.

INT8 KV path: ``k``/``v`` are read as int8, per-(pos, head) ``k_s`` scales
the score tile after QK^T, ``v_s`` scales the probability tile before PV,
and the ``l`` normalizer accumulates unscaled probabilities:
out = (Σ p·v_s·v) / (Σ p) == softmax(s)·v_s·v. No dequantized KV tile ever
exists. A uint16 arena holds raw bf16 words and is bitcast back
(``from_store``) before the f32 upcast.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.kv_layout import NEG_INF, SCALE_ROWS, as_pages, from_store


def _page(b, i, j, tbl, start, bq: int, bk: int):
    """Physical page the KV block (i, j) of row ``b`` reads. Pages past the
    tile's deepest causal limit are skipped in the body; pinning their
    block index to the last visible page means the pipeline never fetches
    them either (an inactive row's start may be negative: the clamp keeps
    the table read in bounds)."""
    last = (start[b] + (i + 1) * bq - 1) // bk
    return tbl[b, jnp.clip(last, 0, j)]


def _kernel(tbl_ref, start_ref, q_ref, k_ref, v_ref, *rest, bq: int, bk: int,
            g: int, n_kv: int, scale: float, quantized: bool):
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    start = start_ref[b]
    hq, hkv = q_ref.shape[2], k_ref.shape[2]
    if quantized:               # the page's row in its tile of scale rows
        row = pl.ds(_page(b, i, j, tbl_ref, start_ref, bq, bk) % SCALE_ROWS,
                    1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # skip KV blocks past the tile's deepest row (absolute causal limit of
    # query i*bq + bq - 1); blocks partially beyond a row's own limit are
    # exact no-ops for that row via the position mask below
    @pl.when(j * bk <= start + (i + 1) * bq - 1)
    def _compute():
        q_pos = start + i * bq + jax.lax.broadcasted_iota(jnp.int32,
                                                          (bq, bk), 0)
        kv_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        visible = kv_pos <= q_pos
        if quantized:            # one load of the row serves every head
            ks_row, vs_row = ks_ref[row, :], vs_ref[row, :]
        for h in range(hkv):
            k = from_store(k_ref[0, :, h, :]).astype(jnp.float32)  # (bk, hd)
            v = from_store(v_ref[0, :, h, :]).astype(jnp.float32)
            for r in range(h * g, (h + 1) * g):
                q = q_ref[0, :, r, :].astype(jnp.float32)           # (bq, hd)
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                if quantized:
                    # dequant on scores
                    s = s * ks_row[:, h * bk:(h + 1) * bk]
                s = jnp.where(visible, s, NEG_INF)
                m_prev, l_prev = m_ref[r], l_ref[r]                 # (bq, 1)
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
                p = jnp.exp(s - m_new)
                corr = jnp.exp(m_prev - m_new)
                l_ref[r] = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
                if quantized:
                    # dequant on probabilities
                    p = p * vs_row[:, h * bk:(h + 1) * bk]
                acc_ref[r] = acc_ref[r] * corr + jax.lax.dot_general(
                    p, v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                m_ref[r] = m_new

    @pl.when(j == n_kv - 1)
    def _finalize():
        for r in range(hq):
            o_ref[0, :, r, :] = (acc_ref[r] / jnp.maximum(l_ref[r], 1e-30)
                                 ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bq", "interpret"))
def paged_prefill_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                                   k_s: Optional[jax.Array] = None,
                                   v_s: Optional[jax.Array] = None,
                                   start: jax.Array = None,
                                   pages: jax.Array = None, *, bq: int = 16,
                                   interpret: bool = False) -> jax.Array:
    """q (B, Sq, Hq, hd) at absolute positions start..start+Sq-1 vs a PAGED
    arena: k/v (n_pages, page_size, Hkv, hd) float, uint16 (raw bf16 words)
    or int8 (then k_s/v_s (n_pages, Hkv*page_size) f32 scales, the arena's
    own layout: nothing is rearranged per call); start (B,)
    int32; pages (B, n_blk) int32 — the window prefix of each row's page
    table. The KV block size is pinned to ``page_size``; grid step (b, i, j)
    DMAs physical page ``pages[b, j]``. Unallocated table entries point at
    physical page 0 (the trash page) and sit beyond every causal limit.
    Ragged chunks pad the query tail to a ``bq`` multiple; the padded rows
    are sliced off. Returns (B, Sq, Hq, hd) bf16."""
    b, sq, hq, hd = q.shape
    ps, hkv = k.shape[1], k.shape[2]
    bq = min(bq, sq)
    pq = (-sq) % bq                          # ragged chunk: padded query tail
    if pq:                                   # rows are sliced off the output
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    n_q = (sq + pq) // bq
    n_blk = pages.shape[1]
    quantized = k_s is not None

    kv_page = functools.partial(_page, bq=bq, bk=ps)

    inputs = [q, k, v]
    in_specs = [
        pl.BlockSpec((1, bq, hq, hd), lambda bb, i, j, tbl, st: (bb, i, 0, 0)),
        pl.BlockSpec((1, ps, hkv, hd),
                     lambda *a: (kv_page(*a), 0, 0, 0)),
        pl.BlockSpec((1, ps, hkv, hd),
                     lambda *a: (kv_page(*a), 0, 0, 0)),
    ]
    if quantized:
        # an arena of any size reads whole tiles (the model's arenas
        # already hold a SCALE_ROWS multiple of pages: no pad there)
        pad = (-k_s.shape[0]) % SCALE_ROWS
        inputs += [jnp.pad(t, ((0, pad), (0, 0))) if pad else t
                   for t in (k_s, v_s)]
        in_specs += [pl.BlockSpec(
            (SCALE_ROWS, hkv * ps),
            lambda *a: (kv_page(*a) // SCALE_ROWS, 0))] * 2

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_q, n_blk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bq, hq, hd),
                               lambda bb, i, j, tbl, st: (bb, i, 0, 0)),
        scratch_shapes=[pltpu.VMEM((hq, bq, 1), jnp.float32),
                        pltpu.VMEM((hq, bq, 1), jnp.float32),
                        pltpu.VMEM((hq, bq, hd), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, bq=bq, bk=ps, g=hq // hkv, n_kv=n_blk,
                          scale=hd ** -0.5, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, sq + pq, hq, hd), jnp.bfloat16),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        # the kernel's name, and the name of its operation in a profile
        name="paged_prefill_attention_pallas",
    )(pages.astype(jnp.int32), jnp.asarray(start, jnp.int32).reshape(b),
      *inputs)
    return out[:, :sq] if pq else out


@functools.partial(jax.jit, static_argnames=("bq", "bk", "interpret"))
def prefill_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                             k_s: Optional[jax.Array] = None,
                             v_s: Optional[jax.Array] = None,
                             start: jax.Array = None, *, bq: int = 16,
                             bk: int = 16,
                             interpret: bool = False) -> jax.Array:
    """q: (B, Sq, Hq, hd) queries at absolute positions start..start+Sq-1;
    k/v: (B, W, Hkv, hd) float or int8 (then k_s/v_s (B, W, Hkv) f32 scales);
    start: (B,) int32 per-slot chunk-start positions. Callers guarantee
    ``W >= start + Sq`` for every row whose output is consumed. The cache is
    read as ``bk``-position pages through an identity table — with ``bk``
    equal to a paged arena's page size (16 by default) the two layouts give
    bit-identical results. Returns (B, Sq, Hq, hd) bf16."""
    k, v, k_s, v_s, pages = as_pages(k, v, k_s, v_s, bk)
    return paged_prefill_attention_pallas(q, k, v, k_s, v_s, start, pages,
                                          bq=bq, interpret=interpret)
