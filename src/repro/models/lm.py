"""Unified decoder LM over heterogeneous block patterns.

One model definition covers all ten assigned architectures: dense GQA
transformers, MoE, Mamba-hybrid (jamba), and xLSTM stacks. The layer pattern
(cfg.pattern x MoE placement) is reduced to its minimal period ``p``; params
are stacked over the ``n_layers/p`` repetitions and the stack is driven by
``lax.scan`` (HLO stays O(p) regardless of depth — compile-time and HLO-size
are depth-independent, which matters at 72 layers x 512 devices).

Decode state is a per-period-position pytree stacked over groups. Recurrent
state and contiguous KV caches are scanned jointly with the params (each
group's slice in ``xs``, written back through ``ys``); a PAGED KV arena
rides in the scan's carry whole, its layers merged into one arena
(``kv_layout.merge_layers``), and each group reads and writes its own
layer's pages through the page table offset by its index, so no layer's
arena is sliced out or written back per step.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.kv_layout import arena_pages, merge_layers, split_layers
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as SSM
from repro.models import xlstm as X
from repro.sharding.ctx import RunContext, default_ctx


# ------------------------------------------------------------------ pattern
def layer_specs(cfg) -> Tuple[Tuple[str, bool], ...]:
    return tuple((kind, cfg.is_moe_layer(i)) for i, kind in enumerate(cfg.pattern))


def pattern_period(cfg) -> int:
    spec = layer_specs(cfg)
    n = len(spec)
    for p in range(1, n + 1):
        if n % p == 0 and all(spec[i] == spec[i % p] for i in range(n)):
            return p
    return n


VOCAB_PAD = 256


def padded_vocab(cfg) -> int:
    """Vocab rounded up so the embedding/unembedding tables shard evenly on
    any mesh axis (granite's 49155 is odd: unpadded it falls back to a fully
    replicated table — an 806 MB f32 read per decode step on the 16x16 mesh;
    EXPERIMENTS.md §Perf granite iteration 2). Padding logits are masked to
    -inf, so the distribution over real tokens is unchanged."""
    return -(-cfg.vocab_size // VOCAB_PAD) * VOCAB_PAD


# ------------------------------------------------------------------ init
def _block_init(key, cfg, kind: str, is_moe: bool) -> dict:
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {"norm1": L.rmsnorm_init(cfg.d_model)}
    if kind == "attn":
        p["attn"] = A.attention_init(ks[0], cfg)
    elif kind == "mamba":
        p["mamba"] = SSM.mamba_init(ks[0], cfg)
    elif kind == "mlstm":
        p["mlstm"] = X.mlstm_init(ks[0], cfg)
        return p
    elif kind == "slstm":
        p["slstm"] = X.slstm_init(ks[0], cfg)
        return p
    else:
        raise ValueError(kind)
    if cfg.d_ff > 0:
        p["norm2"] = L.rmsnorm_init(cfg.d_model)
        if is_moe:
            p["moe"] = M.moe_init(ks[1], cfg)
            if cfg.moe.dense_residual:
                p["mlp"] = L.mlp_init(ks[2], cfg.d_model, cfg.d_ff)
        else:
            p["mlp"] = L.mlp_init(ks[1], cfg.d_model, cfg.d_ff)
    return p


def init_params(key, cfg) -> dict:
    period = pattern_period(cfg)
    groups = cfg.n_layers // period
    spec = layer_specs(cfg)
    k_emb, k_fr, k_blocks = jax.random.split(key, 3)
    v_pad = padded_vocab(cfg)
    params: Dict[str, Any] = {"embed": L.embed_init(k_emb, v_pad,
                                                    cfg.d_model)}
    if not cfg.tie_embeddings:
        params["unembed"] = L.embed_init(
            jax.random.fold_in(k_emb, 1), v_pad, cfg.d_model)
    if cfg.frontend.kind != "none":
        params["frontend"] = L.linear_init(k_fr, cfg.d_model, cfg.d_model)
    layer_keys = jax.random.split(k_blocks, cfg.n_layers)
    per_layer = [_block_init(layer_keys[i], cfg, *spec[i])
                 for i in range(cfg.n_layers)]
    blocks = []
    for j in range(period):
        stacked = jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[per_layer[g * period + j] for g in range(groups)])
        blocks.append(stacked)
    params["blocks"] = tuple(blocks)
    params["final_norm"] = L.rmsnorm_init(cfg.d_model)
    return params


# ------------------------------------------------------------------ blocks
def _ffn_part(p: dict, cfg, x, is_moe: bool, ctx, with_aux: bool):
    if cfg.d_ff <= 0:
        return x, {}
    with jax.named_scope("mlp"):
        h = L.rmsnorm(x, p["norm2"], cfg.norm_eps)
        aux = {}
        if is_moe:
            out, aux = M.moe_forward(p["moe"], cfg, h, ctx, with_aux)
            if cfg.moe.dense_residual:
                out = out + L.mlp(h, p["mlp"])
        else:
            out = L.mlp(h, p["mlp"])
        return x + out, aux


def _block_forward(kind: str, is_moe: bool, p: dict, cfg, x, positions, ctx,
                   cache=None, cur_len=None, with_aux: bool = False,
                   window=None, route=None, pages=None):
    h = L.rmsnorm(x, p["norm1"], cfg.norm_eps)
    new_cache = cache
    if kind == "attn":
        with jax.named_scope("attention"):
            a, new_cache = A.attention_forward(p["attn"], cfg, h, positions,
                                               cache, cur_len, ctx, window,
                                               route, pages)
        x = x + a
        x, aux = _ffn_part(p, cfg, x, is_moe, ctx, with_aux)
    elif kind == "mamba":
        m_out, new_cache = SSM.mamba_forward(p["mamba"], cfg, h, cache)
        x = x + m_out
        x, aux = _ffn_part(p, cfg, x, is_moe, ctx, with_aux)
    elif kind == "mlstm":
        y, new_cache = X.mlstm_forward(p["mlstm"], cfg, h, cache)
        x, aux = x + y, {}
    elif kind == "slstm":
        y, new_cache = X.slstm_forward(p["slstm"], cfg, h, cache)
        x, aux = x + y, {}
    else:
        raise ValueError(kind)
    return x, new_cache, aux


def _shard_x(x, ctx: RunContext):
    if ctx.mesh.size > 1:
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(
                ctx.mesh, P(ctx.batch_spec()[0], None, None)))
    return x


# ------------------------------------------------------------------ forward
def forward(params: dict, cfg, batch: dict, ctx: Optional[RunContext] = None,
            with_aux: bool = False) -> Tuple[jax.Array, dict]:
    """Returns (final hidden states (B, S, d), aux losses)."""
    ctx = ctx or default_ctx()
    x = L.embed_lookup(params["embed"], batch["tokens"])
    if cfg.frontend.kind != "none":
        fr = L.dense(batch["embeds"].astype(L.COMPUTE_DTYPE),
                     params["frontend"])
        x = jnp.concatenate([fr, x], axis=1)
    b, s, _ = x.shape
    positions = jnp.arange(s)
    period = pattern_period(cfg)
    spec = layer_specs(cfg)[:period]
    has_moe = any(m for _, m in spec) and with_aux
    aux0 = ({"load_balance": jnp.zeros((), jnp.float32),
             "router_z": jnp.zeros((), jnp.float32)} if has_moe else {})

    def group(carry, block_params):
        x, aux = carry
        x = _shard_x(x, ctx)
        for j, (kind, is_moe) in enumerate(spec):
            x, _, aux_j = _block_forward(kind, is_moe, block_params[j], cfg,
                                         x, positions, ctx,
                                         with_aux=with_aux)
            if has_moe and aux_j:
                aux = {k: aux[k] + aux_j[k] for k in aux}
        return (x, aux), None

    body = group
    if ctx.remat:
        body = jax.checkpoint(
            group, policy=jax.checkpoint_policies.nothing_saveable)
    (x, aux), _ = jax.lax.scan(body, (x, aux0), params["blocks"])
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return x, aux


def unembed_params(params, cfg):
    return params["embed"] if cfg.tie_embeddings else params["unembed"]


def logits_fn(params, cfg, hidden) -> jax.Array:
    logits = L.unembed(unembed_params(params, cfg), hidden)
    return _mask_pad(logits, cfg)


def _mask_pad(logits, cfg):
    v_pad = logits.shape[-1]
    if v_pad == cfg.vocab_size:
        return logits
    mask = jnp.arange(v_pad) < cfg.vocab_size
    return jnp.where(mask, logits, -1e30)


def loss_fn(params: dict, cfg, batch: dict,
            ctx: Optional[RunContext] = None, with_aux: bool = True,
            ce_chunk: int = 512) -> Tuple[jax.Array, dict]:
    """Next-token CE over text positions, sequence-chunked so the (B,S,V)
    logits tensor is never materialized (peak is (B, ce_chunk, V))."""
    ctx = ctx or default_ctx()
    hidden, aux = forward(params, cfg, batch, ctx, with_aux)
    n_fr = cfg.frontend.n_embeds if cfg.frontend.kind != "none" else 0
    tokens = batch["tokens"]
    b, st = tokens.shape
    # hidden positions n_fr..n_fr+st-2 predict tokens 1..st-1
    h = hidden[:, n_fr:n_fr + st - 1]
    targets = tokens[:, 1:]
    n_tok = h.shape[1]
    ce_chunk = min(ce_chunk, n_tok)
    n_chunks = n_tok // ce_chunk
    rem = n_tok - n_chunks * ce_chunk
    ue = unembed_params(params, cfg)

    def ce(hc, tc):
        lg = _mask_pad(L.unembed(ue, hc), cfg)           # (B, c, V) f32
        lse = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, tc[..., None], axis=-1)[..., 0]
        return jnp.sum(lse - gold)

    def step(acc, xs):
        hc, tc = xs
        return acc + ce(hc, tc), None

    hs = jnp.moveaxis(
        h[:, :n_chunks * ce_chunk].reshape(b, n_chunks, ce_chunk, -1), 1, 0)
    ts = jnp.moveaxis(
        targets[:, :n_chunks * ce_chunk].reshape(b, n_chunks, ce_chunk), 1, 0)
    total, _ = jax.lax.scan(step, jnp.zeros((), jnp.float32), (hs, ts))
    if rem:
        total = total + ce(h[:, n_chunks * ce_chunk:],
                           targets[:, n_chunks * ce_chunk:])
    loss = total / (b * n_tok)
    for v in aux.values():
        loss = loss + v
    return loss, aux


# ------------------------------------------------------------------ decode
def init_decode_state(cfg, batch: int, max_seq: int,
                      ctx: Optional[RunContext] = None,
                      params: Optional[dict] = None,
                      per_slot_pos: bool = False,
                      kv_pages: Optional[Tuple[int, int]] = None) -> dict:
    """Stacked per-period-position caches + current length.

    ``per_slot_pos=True`` makes ``pos`` a (batch,) vector — the layout the
    continuous-batching engine uses, where every batch row ("slot") advances
    independently (``serving.state_pool`` owns slot gather/scatter).

    When ``params`` is given, per-position cache widths (KV heads, Mamba
    channels, mLSTM heads) derive from the param shapes instead of the
    config, so HQP-compacted artifacts — which physically shrank those axes
    — serve without a config rewrite. Compacted stacked families are
    shape-uniform across the layer stack, so one width per period position
    suffices.

    ``kv_pages=(total_pages, page_size)`` switches the KV caches to the
    PAGED arena layout: attention leaves become (n_pages, page_size, Hkv,
    hd), INT8 scales (n_pages, Hkv*page_size), with NO batch/slot axis (the
    arena is shared through per-slot page tables the caller owns —
    ``serving.state_pool``; ``n_pages`` is ``kv_layout.arena_pages`` of
    ``total_pages``, whose spare pages are never used), while recurrent
    Mamba/xLSTM leaves keep their per-slot batch axis (recurrent state is
    O(1) per slot; only position-indexed KV pages)."""
    ctx = ctx or default_ctx()
    period = pattern_period(cfg)
    groups = cfg.n_layers // period
    spec = layer_specs(cfg)[:period]
    hd = cfg.resolved_head_dim

    def stack(make):
        return jax.tree.map(lambda *xs: jnp.stack(xs),
                            *[make() for _ in range(groups)])

    def blk(j):
        return params["blocks"][j] if params is not None else None

    caches = []
    for j, (kind, _) in enumerate(spec):
        if kind == "attn":
            n_kv = (L.out_features(blk(j)["attn"]["wk"]) // hd
                    if params is not None else cfg.n_kv_heads)
            kv_b, kv_s = ((arena_pages(kv_pages[0]), kv_pages[1])
                          if kv_pages is not None else (batch, max_seq))
            caches.append(stack(lambda: A.init_kv_cache(
                kv_b, kv_s, n_kv, hd, ctx.quantized_kv,
                paged=kv_pages is not None)))
        elif kind == "mamba":
            d_in = (blk(j)["mamba"]["conv_w"].shape[-1]
                    if params is not None else None)
            caches.append(stack(
                lambda: SSM.init_mamba_state(batch, cfg, d_in=d_in)))
        elif kind == "mlstm":
            d_in = (L.out_features(blk(j)["mlstm"]["in_proj"]) // 2
                    if params is not None else None)
            caches.append(stack(
                lambda: X.init_mlstm_state(batch, cfg, d_in=d_in)))
        elif kind == "slstm":
            caches.append(stack(lambda: X.init_slstm_state(batch, cfg)))
    pos = (jnp.zeros((batch,), jnp.int32) if per_slot_pos
           else jnp.zeros((), jnp.int32))
    return {"caches": tuple(caches), "pos": pos}


def decode_step(params: dict, cfg, state: dict, tokens: jax.Array,
                ctx: Optional[RunContext] = None,
                embeds: Optional[jax.Array] = None,
                window: Optional[int] = None,
                route: Optional[str] = None) -> Tuple[jax.Array, dict]:
    """tokens: (B, S_new) (S_new=1 for decode, >1 for cache-filling prefill).

    ``state["pos"]`` is a scalar (whole batch at one position — the serial
    serve path) or a (B,) vector of per-slot positions (the continuous-
    batching engine, where each slot is mid-way through its own request).
    With a vector pos, rope positions and KV-cache writes/masks are all
    slot-indexed; the recurrent (Mamba/xLSTM) states are position-free and
    need no change.

    ``embeds``: optional precomputed frontend embeddings, prepended during
    prefill (VLM patches / audio frames).

    ``window``: STATIC visible-window bound on KV-cache attends (host-side
    callers bucket ``max(pos)+S_new`` up to a block multiple — the engine's
    length-aware path); None attends the whole ``max_seq`` buffer. Windowed
    and full attends are bit-identical (masked positions contribute exact
    zeros); jitted callers must mark ``window`` static.

    ``route``: STATIC attend route for the KV attend — ``"prefill"`` |
    ``"decode"`` (None infers: S_new == 1 -> decode, else prefill). Chunked-
    prefill callers (the engine) pass ``route="prefill"`` explicitly so a
    1-token tail chunk stays on the ``prefill_attention`` primitive instead
    of being shape-inferred onto the decode kernel — see
    ``attention_forward``. Returns (logits, new state).

    ``state["pages"]`` (B, max_pages) int32, when present, marks the KV
    caches as PAGED arenas (``init_decode_state(kv_pages=...)``): every KV
    write and attend indirects through the per-row page table. The table is
    an INPUT only — the returned state is always ``{"caches", "pos"}``;
    callers that page re-attach the table they own on the next call
    (``serving.engine`` redirects inactive rows to the trash page between
    dispatches, which a pass-through here would silently undo)."""
    ctx = ctx or default_ctx()
    x = L.embed_lookup(params["embed"], tokens)
    if embeds is not None and cfg.frontend.kind != "none":
        fr = L.dense(embeds.astype(L.COMPUTE_DTYPE), params["frontend"])
        x = jnp.concatenate([fr, x], axis=1)
    b, s, _ = x.shape
    cur = state["pos"]
    pages = state.get("pages")
    positions = (cur + jnp.arange(s) if jnp.ndim(cur) == 0
                 else cur[:, None] + jnp.arange(s)[None, :])
    period = pattern_period(cfg)
    groups = cfg.n_layers // period
    spec = layer_specs(cfg)[:period]
    # paged KV arenas ride in the carry, each stack merged into one arena
    # that every group reads and writes in place through its offset table;
    # recurrent state and contiguous caches are sliced per group
    caches = state["caches"]
    carried = tuple(pages is not None and A.is_kv_entry(e) for e in caches)
    arenas, n_pages = zip(*(merge_layers(e) if c else (None, 0)
                            for e, c in zip(caches, carried)))
    scanned = tuple(None if c else e for e, c in zip(caches, carried))

    def group(carry, xs):
        x, arenas = carry
        block_params, caches, g = xs
        x = _shard_x(x, ctx)
        arenas, new_caches = list(arenas), []
        for j, (kind, is_moe) in enumerate(spec):
            cache, pg = ((arenas[j], pages + g * n_pages[j]) if carried[j]
                         else (caches[j], pages))
            x, nc, _ = _block_forward(kind, is_moe, block_params[j], cfg, x,
                                      positions, ctx, cache, cur,
                                      window=window, route=route, pages=pg)
            if carried[j]:
                arenas[j], nc = nc, None
            new_caches.append(nc)
        return (x, tuple(arenas)), tuple(new_caches)

    (x, arenas), new_caches = jax.lax.scan(
        group, (x, arenas),
        (params["blocks"], scanned, jnp.arange(groups, dtype=jnp.int32)))
    new_caches = tuple(split_layers(a, groups) if c else n
                       for a, n, c in zip(arenas, new_caches, carried))
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    with jax.named_scope("logits"):
        logits = logits_fn(params, cfg, x)
    return logits, {"caches": new_caches, "pos": cur + s}


def verify_step(params: dict, cfg, state: dict, tokens: jax.Array,
                ctx: Optional[RunContext] = None,
                window: Optional[int] = None) -> Tuple[jax.Array, dict]:
    """Multi-position verification scoring for speculative decoding.

    ``tokens`` is the (B, K+1) candidate chunk ``[t0, d1..dK]`` — the last
    accepted token followed by the drafter's K proposals. One
    ``route="prefill"`` pass scores ALL K+1 positions: ``logits[:, i]`` is
    the verifier's next-token distribution after consuming ``tokens[:, i]``,
    i.e. the target distribution draft ``d_{i+1}`` is judged against (and
    ``logits[:, K]`` is the bonus-token distribution when every draft
    accepts). Because the prefill route shares the serial decode route's
    absolute-position causal semantics (DESIGN.md §10), position ``i`` of
    this chunk is bit-identical to what a serial one-token-at-a-time decode
    of the same prefix would produce — the hinge of the speculative greedy
    token-identity guarantee.

    The returned state has advanced ``pos`` by K+1 and written KV for every
    candidate; the caller (``serving.speculative``) rolls ``pos`` back to
    the accepted length — stale KV past the rolled-back ``pos`` is masked
    by the absolute causal limit of every later attend and overwritten
    before it can become visible, exactly like slot reuse in the pool."""
    return decode_step(params, cfg, state, tokens, ctx, window=window,
                       route="prefill")
