"""GQA attention with three STATIC attend routes: train | prefill | decode.

Train (no cache) uses an online-softmax KV-chunked scan (pure jnp, XLA path —
its FLOPs/bytes are visible to ``cost_analysis`` for the roofline; the Pallas
TPU kernel in ``repro.kernels.flash_attention`` is the deployment hot path
validated against it).

Prefill and decode attend a (possibly INT8-quantized) KV cache laid out
(B, S, Hkv, hd) through the backend ``prefill_attention`` /
``decode_attention`` primitives (Pallas cache-continuation / split-KV
kernels on TPU; the masked einsum on xla). The sequence axis can be sharded
across the ``model`` mesh axis (flash-decoding style sequence parallelism:
local partial softmax stats + tiny cross-shard reductions, inserted
automatically by GSPMD).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.models import layers as L

NEG_INF = -1e30


# ------------------------------------------------------------------ params
def attention_init(key, cfg) -> dict:
    hd = cfg.resolved_head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": L.linear_init(k1, cfg.d_model, cfg.n_heads * hd),
        "wk": L.linear_init(k2, cfg.d_model, cfg.n_kv_heads * hd),
        "wv": L.linear_init(k3, cfg.d_model, cfg.n_kv_heads * hd),
        "wo": L.linear_init(k4, cfg.n_heads * hd, cfg.d_model),
    }


def _split_heads(x, n_heads, hd):
    b, s, _ = x.shape
    return x.reshape(b, s, n_heads, hd)


# ------------------------------------------------------------------ flash
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, chunk_kv: int = 1024,
                    q_offset: int = 0) -> jax.Array:
    """Online-softmax attention, KV-chunked.

    q: (B, Sq, Hq, hd); k, v: (B, Skv, Hkv, hd), Hq = G * Hkv. Skv may be
    ragged (any length): K/V are zero-padded to a ``chunk_kv`` multiple and
    the tail is masked by position. Returns (B, Sq, Hq, hd). Scores and
    stats in f32.

    Causal semantics are ABSOLUTE-position: query i sits at position
    ``q_offset + i`` and sees ``kv_pos <= q_offset + i``. The default
    ``q_offset=0`` means queries are the FIRST Sq positions — the same
    convention as ``kernels.ref.flash_attention_ref`` and the ``start``
    argument of the cache-attention primitives (there is exactly one
    Sq<Skv convention in the repo; tests cross-check all three).
    """
    b, sq, hq, hd = q.shape
    _, skv, hkv, _ = k.shape
    g = hq // hkv
    scale = hd ** -0.5
    chunk_kv = min(chunk_kv, skv)
    pad_kv = (-skv) % chunk_kv        # ragged Skv (prime lengths, odd prompt
    if pad_kv:                        # sizes): zero-pad, mask the tail below
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
    n_chunks = (skv + pad_kv) // chunk_kv

    # bf16 operands, f32 accumulation (MXU native mode).
    qs = (q.astype(jnp.float32) * scale).astype(L.COMPUTE_DTYPE)
    qs = qs.reshape(b, sq, hkv, g, hd)
    kc = k.reshape(b, n_chunks, chunk_kv, hkv, hd)
    vc = v.reshape(b, n_chunks, chunk_kv, hkv, hd)

    q_pos = q_offset + jnp.arange(sq)

    def step(carry, xs):
        m, l, acc = carry
        k_j, v_j, j = xs
        kv_pos = j * chunk_kv + jnp.arange(chunk_kv)
        # scores: (B, Sq, Hkv, G, Ckv)
        s = jnp.einsum("bqhgd,bchd->bqhgc", qs, k_j,
                       preferred_element_type=jnp.float32)
        # padded tail positions are masked unconditionally (the causal limit
        # alone would leave them visible to queries past skv-1)
        mask = jnp.broadcast_to(kv_pos[None, :] < skv, (sq, chunk_kv))
        if causal:
            mask = mask & (kv_pos[None, :] <= q_pos[:, None])  # (Sq, Ckv)
        if causal or pad_kv:
            s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bqhgc,bchd->bqhgd", p.astype(L.COMPUTE_DTYPE), v_j,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, sq, hkv, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, sq, hkv, g), jnp.float32)
    acc0 = jnp.zeros((b, sq, hkv, g, hd), jnp.float32)
    ks = jnp.moveaxis(kc, 1, 0)
    vs = jnp.moveaxis(vc, 1, 0)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, acc0),
                                  (ks, vs, jnp.arange(n_chunks)))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(b, sq, hq, hd).astype(L.COMPUTE_DTYPE)


# ------------------------------------------------------------------ KV cache
@dataclasses.dataclass
class CacheSpec:
    quantized: bool = False     # INT8 KV cache (beyond-paper: HQP applied to KV)


def is_kv_entry(entry) -> bool:
    """True for a position-indexed KV cache entry (pageable); False for
    recurrent Mamba/xLSTM state (slot-resident, O(1) per slot)."""
    return isinstance(entry, dict) and ("k" in entry or "k_q" in entry)


def init_kv_cache(batch: int, max_seq: int, n_kv_heads: int, hd: int,
                  quantized: bool = False, paged: bool = False) -> dict:
    """``paged=True`` marks the leaves as a shared page arena (``batch`` is
    ``total_pages``, ``max_seq`` is ``page_size``); its INT8 scales are
    (total_pages, Hkv*page_size), the layout the cache-attention kernel
    reads (``kernels.kv_layout``). bf16 caches — paged
    arena and contiguous pool alike — are stored as raw uint16 words: XLA
    CPU's float-normalization pass rewrites bf16 scatter/dynamic_update_
    slice through f32 converts, copying the whole buffer on every write —
    uint16 data movement stays in place under donation
    (``kernels.kv_layout.to_store/from_store`` own the lossless bitcasts at
    the read/write boundaries). int8 quantized leaves scatter in place
    natively and keep their dtype in both layouts."""
    if quantized:
        s_shape = ((batch, n_kv_heads * max_seq) if paged
                   else (batch, max_seq, n_kv_heads))
        return {
            "k_q": jnp.zeros((batch, max_seq, n_kv_heads, hd), jnp.int8),
            "v_q": jnp.zeros((batch, max_seq, n_kv_heads, hd), jnp.int8),
            "k_s": jnp.zeros(s_shape, jnp.float32),
            "v_s": jnp.zeros(s_shape, jnp.float32),
        }
    dt = (jnp.uint16 if L.COMPUTE_DTYPE == jnp.bfloat16
          else L.COMPUTE_DTYPE)
    return {
        "k": jnp.zeros((batch, max_seq, n_kv_heads, hd), dt),
        "v": jnp.zeros((batch, max_seq, n_kv_heads, hd), dt),
    }


def _quant_kv(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per (batch, pos, head) symmetric int8. x: (B, S, Hkv, hd)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    s = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s[..., None]), -127, 127)
    return q.astype(jnp.int8), s


def update_kv_cache(cache: dict, k_new: jax.Array, v_new: jax.Array,
                    pos: jax.Array,
                    pages: Optional[jax.Array] = None) -> dict:
    """Insert (B, Sn, Hkv, hd) at position ``pos``.

    ``pos`` is a scalar (all rows write at the same offset — the single-batch
    serve path) or a (B,) vector of per-slot offsets (the continuous-batching
    engine, where every slot sits at its own sequence position). The vector
    path is a per-row scatter (vmapped dynamic_update_slice).

    ``pages`` (B, max_pages) int32 marks the cache as a PAGED arena —
    leaves are (n_pages, page_size, ...) with no batch axis, scales (n_pages,
    Hkv*page_size) — and the write becomes a per-token scatter through the
    page table (``kv_layout.scatter_pages``): logical position
    p of row b lands at ``arena[pages[b, p // page_size], p % page_size]``.
    The model passes the layer stack merged into one arena
    (``kv_layout.merge_layers``) with the table offset to the layer's
    pages, so the scatter writes the stack in place. The per-token
    values (INT8 quant included — it is per-(pos, head)) are identical to
    the contiguous write, which is what keeps prefix-cache page reuse
    bit-exact across requests."""
    with jax.named_scope("kv_write"):
        if "k_q" in cache:
            kq, ks = _quant_kv(k_new)
            vq, vs = _quant_kv(v_new)
            new = {"k_q": kq, "v_q": vq, "k_s": ks, "v_s": vs}
        else:
            # bf16 caches store raw uint16 words (init_kv_cache) —
            # scatter_pages bitcasts the update itself, so keep it in
            # compute dtype there; the contiguous DUS paths bitcast here
            new = {"k": k_new.astype(L.COMPUTE_DTYPE),
                   "v": v_new.astype(L.COMPUTE_DTYPE)}
        if pages is not None:
            from repro.kernels.kv_layout import (scatter_page_scales,
                                                 scatter_pages)
            ps = cache["k_q" if "k_q" in cache else "k"].shape[1]
            return {key: (scatter_page_scales(cache[key], new[key], pages,
                                              pos, ps)
                          if key in ("k_s", "v_s")
                          else scatter_pages(cache[key], new[key], pages,
                                             pos))
                    for key in cache}
        from repro.kernels.kv_layout import to_store
        new = {key: to_store(val, cache[key].dtype)
               for key, val in new.items()}
        if jnp.ndim(pos) == 0:
            def scatter(buf, upd):
                idx = (0, pos) + (0,) * (buf.ndim - 2)
                return jax.lax.dynamic_update_slice(buf, upd, idx)
        else:
            def scatter(buf, upd):
                def row(b_row, u_row, p):
                    idx = (p,) + (0,) * (b_row.ndim - 1)
                    return jax.lax.dynamic_update_slice(b_row, u_row, idx)
                return jax.vmap(row)(buf, upd, pos)
        return {key: scatter(cache[key], new[key]) for key in cache}


def cached_attention(q: jax.Array, cache: dict, start: jax.Array,
                     window: Optional[int] = None) -> jax.Array:
    """Masked-einsum oracle for cache attention (NOT the hot path — the
    ``prefill_attention``/``decode_attention`` backend primitives are; on
    the ``xla`` backend they resolve to exactly this einsum).

    q: (B, Sq, Hq, hd) queries at absolute positions start..start+Sq-1,
    attending a cache that already holds positions [0, start+Sq). ``start``
    is scalar or (B,) (per-slot positions under continuous batching). Query
    i attends cache positions <= start+i — the absolute causal limit every
    attend route in the repo shares, which is why a chunked prefill produces
    bit-identical logits to a whole-prompt prefill.

    ``window`` (STATIC int, host-bucketed >= start+Sq, None = full buffer)
    restricts the einsum to the visible prefix, so traffic is O(window)
    instead of O(max_seq) — positions past the window contribute
    exp(-inf) = 0 exactly, keeping the windowed path bit-identical to the
    full-mask einsum. The INT8 cache is read as int8; per-(pos,head) dequant
    rides on the score/probability matrices."""
    return ops.cached_attention(q, cache, start, window)


# ------------------------------------------------------------------ block fwd
def _context_parallel(q, k, v, ctx):
    """Re-shard attention over the SEQUENCE instead of heads.

    When n_kv_heads doesn't divide the TP width (GQA-8 on a 16-wide model
    axis — six of the ten assigned archs), GSPMD splits the head_dim across
    ranks and must all-reduce full f32 score tensors every KV chunk
    (arctic-480b: 1.9 GB x 140 per step — EXPERIMENTS.md §Perf iteration 3).
    Sharding queries over (model=sequence) keeps every score tile local; the
    price is one KV broadcast per layer (B·S·Hkv·hd bf16, ≪ the scores)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    bspec = ctx.batch_spec()[0]
    mdl = ctx.model_axis
    sh = lambda t, spec: jax.lax.with_sharding_constraint(
        t, NamedSharding(ctx.mesh, spec))
    q = sh(q, P(bspec, mdl, None, None))
    k = sh(k, P(bspec, None, None, None))
    v = sh(v, P(bspec, None, None, None))
    return q, k, v


TRAIN, PREFILL, DECODE = "train", "prefill", "decode"
ROUTES = (TRAIN, PREFILL, DECODE)


def attention_forward(p: dict, cfg, x: jax.Array, positions: jax.Array,
                      cache: Optional[dict] = None,
                      cur_len: Optional[jax.Array] = None,
                      ctx=None, window: Optional[int] = None,
                      route: Optional[str] = None,
                      pages: Optional[jax.Array] = None,
                      ) -> Tuple[jax.Array, Optional[dict]]:
    """Full attention sub-block (no norm/residual — block owns those).

    ``route`` is the STATIC attend route — ``"train" | "prefill" |
    "decode"`` — replacing the old fragile boolean ``decode`` tri-state
    (where a 1-token prefill tail chunk had to remember to pass
    ``decode=False`` or silently take kernel numerics that break the
    engine's token-identity contract). The three routes:

      train    cache is None: local flash attention over the fresh K/V
      prefill  cache given, x is (B, Sq, d): write K/V, attend the cache
               through the backend ``prefill_attention`` primitive —
               Sq == 1 (a prompt's tail chunk) is legal and STAYS here
      decode   cache given, x is (B, 1, d): write K/V, attend through the
               backend ``decode_attention`` primitive

    ``route=None`` infers: no cache -> train; else S == 1 -> decode, S > 1
    -> prefill. Engine/serving callers pass the route explicitly — the
    inference is a convenience for serial/test code, and a tail chunk left
    to inference would (correctly for serial, wrongly for chunked prefill)
    land on decode, which is why the engine never relies on it.

    ``window``: static visible-window bound (see ``ops``) — cache writes
    always hit the full buffer, only the attend is windowed. ``cur_len`` =
    tokens already in cache (scalar or (B,) per-slot).

    ``pages`` (B, max_pages) int32: the cache is a PAGED arena — both the
    KV write and the attend indirect through the page table (``ops`` owns
    the window-as-page-prefix plumbing; the train route never pages).
    """
    hd = cfg.resolved_head_dim
    b, s, _ = x.shape
    # head counts derive from (possibly HQP-compacted) param shapes
    n_heads = L.out_features(p["wq"]) // hd
    n_kv = L.out_features(p["wk"]) // hd
    # with qk-norm the projections reach the norm as f32: XLA on a TPU
    # skips the bf16 rounding of a projection where it fuses it with the
    # norm, so a bf16 result would be rounded or not depending on the
    # program around it, and the paged engine's K would differ from serial
    # decode's
    acc = jnp.float32 if cfg.qk_norm else None
    q = _split_heads(L.dense(x, p["wq"], acc), n_heads, hd)
    k = _split_heads(L.dense(x, p["wk"], acc), n_kv, hd)
    v = _split_heads(L.dense(x, p["wv"]), n_kv, hd)
    if cfg.qk_norm:
        q, k = L.l2norm(q), L.l2norm(k)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)

    use_cp = (s > 1 and ctx is not None and not ctx.pure_dp
              and ctx.mesh.size > 1 and ctx.tp_size > 1
              and n_kv % ctx.tp_size != 0 and s % ctx.tp_size == 0)
    if use_cp:
        q, k, v = _context_parallel(q, k, v, ctx)

    assert route is None or route in ROUTES, route
    if cache is None:
        assert route in (None, TRAIN), \
            f"route {route!r} needs a cache; got none"
        o = flash_attention(q, k, v, causal=True, chunk_kv=cfg.attn_chunk_kv)
        new_cache = None
    else:
        assert route != TRAIN, "train route cannot take a cache"
        # cache-filling prefill (s > 1) and decode (s == 1) share the same
        # semantics: write K/V, then attend the cache with per-query causal
        # limits — both through backend primitives (Pallas online-softmax
        # kernels on TPU; the xla registration of either primitive is the
        # identical masked einsum). Chunked prefill continuation
        # (cur_len > 0) needs the cache read — a local flash attend would
        # miss the earlier chunks.
        new_cache = update_kv_cache(cache, k, v, cur_len, pages=pages)
        r = route or (DECODE if s == 1 else PREFILL)
        if r == DECODE:
            assert s == 1, f"decode attend requires a single query, got {s}"
            o = ops.decode_attention(q, new_cache, cur_len, window, pages)
        else:
            o = ops.prefill_attention(q, new_cache, cur_len, window, pages)
    out = L.dense(o.reshape(b, s, n_heads * hd), p["wo"])
    return out, new_cache
