"""Continuous-batching serving engine over HQP artifacts.

The ``Engine`` owns a slot-based batch of ``n_slots`` concurrent requests.
Requests are admitted into free slots on arrival, prefilled in chunks
interleaved with batched decode steps (``serving.scheduler`` owns the
policy), and evicted on EOS / length — freeing the slot for the next waiting
request. All device work goes through a fixed set of jitted callables with
a **static slot count**:

  _reset_fn  (pool, slot, template)          admission: zero one slot
  _prefill_fn(params, pool, slot, chunk, window)
                                             one prompt chunk into one slot
  _decode_fn (params, pool, tokens, active, eos, budget, window)
                                             ``decode_steps`` batched steps
                                             entirely on device (lax.scan)
  _spec_prefill_fn / SpecDecoder.spec_fn     the speculative mode's fused
                                             dual-pool prefill and
                                             draft->verify cycles (§11)

so steady-state serving never retraces (prefill compiles once per distinct
(chunk length, window bucket); decode once per window bucket). The state
pool is built on ``init_decode_state(..., params=...)``: HQP-compacted
artifacts size their own caches, and ``QuantizedLinear`` weights dispatch
through the kernels/backend registry exactly as on the serial path.

Two length-aware fast paths (DESIGN.md §10):

  * every KV attend carries a STATIC ``window`` — the live sequence bound
    bucketed to ``SchedulerConfig.window_block`` — so decode/prefill traffic
    scales with actual sequence length, not cache capacity;
  * decode runs ``SchedulerConfig.decode_steps`` greedy steps per dispatch
    inside a jitted ``lax.scan``: on-device argmax, token feedback, and
    per-slot EOS/length stop flags (stopped slots are select-masked frozen),
    with ONE host sync per scan to harvest the emitted tokens — not one per
    token (``stats["host_syncs"]`` vs ``stats["device_steps"]`` makes the
    ratio observable).

Token-identity contract: engine outputs are bit-identical to serial
single-request decode because (a) every per-slot computation is independent
across the batch axis, (b) chunked prefill and decode attend the cache
through the SAME backend primitives the serial path resolves to
(``prefill_attention`` / ``decode_attention``), whose causal limits are
absolute positions — so chunk boundaries, query-tile sizes, and window
buckets all yield bit-identical logits (out-of-window/limit positions
contribute exact zeros) — and (c) inactive/stopped slots are select-masked
back to their pre-step state after every batched decode step, on device.
XLA on TPU is not batch-invariant (a one-row program can round differently
from an ``n_slots``-row one), so the serial reference decodes at the
engine's slot width (``serial_decode(decode_rows=n_slots)``).

Beyond greedy lockstep, the engine carries two optional modes (both
preserving the identity contract in their greedy forms): seeded
temperature/top-k sampling (``serving.sampling`` — keys derive from seed x
absolute position, so engine and serial draws coincide) and SELF-
SPECULATIVE decoding (``serving.speculative``, DESIGN.md §11 — the HQP
artifact drafts ``spec_k`` tokens per cycle over its own compacted pool,
the bf16 parent verifies all of them in one ``prefill``-route pass, and
greedy output stays bit-identical to serial bf16 decode).

``REPRO_DEBUG_WINDOW=1`` arms a host-side assert in ``step()`` that catches
an undersized static window (< start + Sq) before dispatch — without it a
miscomputed window silently truncates the visible cache and produces wrong
tokens with no error.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
import os
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.analysis.invariants import declare_invariants
from repro.kernels.kv_layout import page_count
from repro.models import lm
from repro.serving import sampling as smp
from repro.serving import state_pool as sp
from repro.serving.scheduler import (DECODE, PREFILL, Scheduler,
                                     SchedulerConfig)
from repro.serving.speculative import SpecDecoder
from repro.sharding.ctx import RunContext, default_ctx

FREE = "free"


@dataclasses.dataclass
class Request:
    """One generation request (token ids in, token ids out; greedy).

    ``uid`` is engine-assigned at submit() (the return value); any value set
    by the caller is ignored for identity."""
    prompt: Sequence[int]
    max_new_tokens: int
    eos_id: Optional[int] = None
    uid: Optional[int] = None


@dataclasses.dataclass
class RequestResult:
    uid: int
    prompt_len: int
    tokens: List[int]                 # generated ids (EOS included if hit)
    finish_reason: str                # "eos" | "length"
    t_submit: float
    t_admit: float = 0.0
    t_first_token: float = 0.0
    t_finish: float = 0.0

    @property
    def ttft_s(self) -> float:
        return self.t_first_token - self.t_submit

    @property
    def latency_s(self) -> float:
        return self.t_finish - self.t_submit


@dataclasses.dataclass
class _Slot:
    idx: int
    stage: str = FREE                 # free | prefill | decode
    prompt: Optional[np.ndarray] = None
    prefill_done: int = 0
    last_token: int = 0
    prev_token: int = 0               # token at pos-1 (speculative healing
                                      # chunk re-feeds [prev, last])
    result: Optional[RequestResult] = None
    eos_id: Optional[int] = None
    max_new_tokens: int = 0
    pages: List[int] = dataclasses.field(default_factory=list)
    n_shared: int = 0                 # leading pages also referenced by the
                                      # prefix cache / other slots: written
                                      # only after copy-on-write


def _kv_bytes(pool) -> int:
    """Total device bytes of the position-indexed KV entries of a pool
    (recurrent state excluded) — the quantity paging exists to shrink."""
    return sum(leaf.nbytes
               for entry in pool["caches"] if sp.is_kv_entry(entry)
               for leaf in jax.tree_util.tree_leaves(entry))


def _pick_token(logits_row, pos: int, sampler) -> int:
    """Host-side token pick shared by every single-row emission surface
    (engine prefill tails, serial_decode). ``sampler=None`` is greedy:
    host ``np.argmax``, the pre-sampling bitwise path. A non-None sampler
    is the jitted position-keyed draw — ``pos`` is the absolute position
    the token's KV will be written at, the key-derivation rule every
    sampling surface shares."""
    if sampler is None:
        return int(np.argmax(np.asarray(logits_row)))
    return int(sampler(logits_row, jnp.int32(pos)))


class Engine:
    """Continuous-batching engine serving a (possibly HQP-quantized) LM."""

    def __init__(self, params: Any, cfg, ctx: Optional[RunContext] = None,
                 n_slots: int = 4, max_seq: int = 128,
                 sched: Optional[SchedulerConfig] = None,
                 sampling: Optional[smp.SamplingConfig] = None,
                 draft_params: Any = None, spec_k: int = 4,
                 spec_cycles: int = 1,
                 draft_ctx: Optional[RunContext] = None,
                 draft_manifest=None, page_size: Optional[int] = None,
                 total_pages: Optional[int] = None,
                 prefix_cache: bool = True,
                 clock=telemetry.default_clock):
        """``sampling``: temperature/top-k/seeded sampling for every decode
        surface (None = greedy, the bit-identical-to-serial default).

        ``clock``: the injectable monotonic clock behind every timestamp
        the engine takes — request lifecycle times, per-step phase
        attribution (``last_step``), and span recording. The service
        layer re-points it at its own clock on attach so one fake clock
        drives the whole plane in tests.

        ``draft_params`` switches on SPECULATIVE mode: ``params`` becomes
        the verifier (bf16 parent), ``draft_params`` the drafter (the HQP
        artifact), and each decode dispatch runs ``spec_cycles`` speculative
        cycles — ``spec_k`` draft steps + one multi-position verify each —
        instead of ``decode_steps`` verifier steps. ``draft_ctx`` sizes the
        drafter's
        own pool (INT8 KV for an artifact drafter); ``draft_manifest``
        (the artifact's ``HQPManifest``) is checked for vocab/arch
        compatibility before any device work.

        ``page_size`` switches on PAGED KV (DESIGN.md §12): the per-slot KV
        pool becomes a global arena of ``total_pages`` fixed-size pages
        (default: full provisioning, ``1 + n_slots *
        ceil(max_seq/page_size)`` — one extra for the trash page) with a
        host-side free-list allocator and per-slot page tables. Pages are
        allocated covering the prompt at admission and grown on demand
        before each decode dispatch; ``prefix_cache=True`` additionally
        keys completed page-aligned prompt heads by content hash so a
        repeated prompt head maps the cached pages copy-free and prefills
        only its tail. ``page_size == max_seq`` is the contiguous-identity
        degenerate case (one page per slot). Outputs stay token-identical
        to the contiguous pool at every page size."""
        if cfg.frontend.kind != "none":
            raise NotImplementedError(
                "Engine v1 serves token-only archs; frontend (VLM/audio) "
                "requests need per-slot embed plumbing — a later PR")
        self.params = params
        self.cfg = cfg
        self.ctx = ctx or default_ctx()
        self.n_slots = n_slots
        self.max_seq = max_seq
        self.scheduler = Scheduler(sched)
        self.sampling = sampling or smp.GREEDY
        self.paged = page_size is not None
        self.page_size = page_size if self.paged else max_seq
        if self.paged:
            if page_size < 1:
                raise ValueError(f"page_size must be >= 1, got {page_size}")
            self.max_pages = page_count(max_seq, page_size)
            if total_pages is None:
                total_pages = 1 + n_slots * self.max_pages
            self.total_pages = total_pages
            self.alloc = sp.PageAllocator(total_pages)
            self.prefix = (sp.PrefixCache(self.alloc, page_size)
                           if prefix_cache else None)
            # host mirror of every slot's page table; device copies are
            # cached per (state, active mask) in ``_dispatch_table`` (rows
            # of inactive slots redirected to the trash page)
            self.table = np.zeros((n_slots, self.max_pages), np.int32)
            self.pool = sp.init_paged_pool(cfg, n_slots, max_seq, self.ctx,
                                           params=params,
                                           page_size=page_size,
                                           total_pages=total_pages)
        else:
            self.alloc = None
            self.prefix = None
            self.pool = sp.init_pool(cfg, n_slots, max_seq, self.ctx,
                                     params=params)
            # contiguous dispatches still feed the (ignored) table operand
            # so both modes share one set of jitted callables
            self.table = np.zeros((n_slots, 1), np.int32)
        self._table_cache: dict = {}    # device tables, see _dispatch_table
        self._template = sp.init_slot_template(cfg, max_seq, self.ctx,
                                               params=params)
        self.spec: Optional[SpecDecoder] = None
        if draft_params is not None:
            self.spec = SpecDecoder(cfg, draft_params, params, ctx=self.ctx,
                                    draft_ctx=draft_ctx, k=spec_k,
                                    cycles=spec_cycles,
                                    sampling=self.sampling,
                                    draft_manifest=draft_manifest,
                                    paged=self.paged)
            dctx = self.spec.draft_ctx
            if self.paged:
                # ONE allocator + table addresses both arenas: the pools'
                # positions stay aligned, so page p holds the same token
                # span in the drafter and verifier arenas
                self.draft_pool = sp.init_paged_pool(
                    cfg, n_slots, max_seq, dctx, params=draft_params,
                    page_size=page_size, total_pages=total_pages)
            else:
                self.draft_pool = sp.init_pool(cfg, n_slots, max_seq, dctx,
                                               params=draft_params)
            self._draft_template = sp.init_slot_template(cfg, max_seq, dctx,
                                                         params=draft_params)
        kv_bytes = _kv_bytes(self.pool) + (
            _kv_bytes(self.draft_pool) if self.spec is not None else 0)
        if self.paged:
            self._kv_page_bytes = kv_bytes // total_pages
            self._kv_token_bytes = self._kv_page_bytes // self.page_size
        else:
            self._kv_token_bytes = kv_bytes // (n_slots * max_seq)
        self.slots = [_Slot(i) for i in range(n_slots)]
        self.waiting: List[Request] = []
        self._uid = itertools.count()
        self.ticks = 0
        self.clock = clock
        # optional telemetry.SpanRecorder — a passive sink fed engine
        # timestamps; None costs nothing on the hot path
        self.tracer: Optional[telemetry.SpanRecorder] = None
        # per-step measurement surface: {"wall_s", "phases",
        # "prefill_tokens", "decode_tokens"} — the service feeds the
        # admission EWMA and the phase histograms from this instead of
        # re-measuring around step()
        self.last_step: Optional[dict] = None
        self._ph: Dict[str, float] = {}
        # optional per-token sink (the service layer's streaming hook):
        # called as on_token(uid, token) from _emit for EVERY emitted token,
        # before finish bookkeeping — so a streaming front door sees tokens
        # at host-sync granularity instead of waiting for the full result
        self.on_token = None
        # drafted_tokens counts every candidate the device produced for a
        # slot that was live at dispatch (speculative drafts, or plain-mode
        # scan steps — including steps burned on slots that froze mid-scan,
        # the device work the old stats under-counted); accepted_tokens
        # counts the candidates that became emitted request tokens
        # (speculative corrections are emitted but NOT accepted drafts), so
        # acceptance rate = accepted_tokens / drafted_tokens from stats
        # alone, in both modes.
        self.stats = {"prefill_ticks": 0, "decode_ticks": 0,
                      "decode_slot_steps": 0, "prefill_tokens": 0,
                      "host_syncs": 0, "device_steps": 0,
                      "drafted_tokens": 0, "accepted_tokens": 0,
                      "prefix_hits": 0, "prefix_hit_tokens": 0,
                      "bytes_saved": 0, "cow_copies": 0,
                      "pages_in_use": 0, "pages_peak": 0,
                      "cancelled": 0, "faults": 0,
                      "kv_bytes_peak": 0 if self.paged else kv_bytes}
        # fault attribution for request-scoped isolation (see step):
        # ("admit", request) | ("slots", [idx, ...]) | None, set just
        # before each fallible phase so _absorb_fault knows the blast
        # radius of whatever raised
        self._fault_phase = None
        # the exception behind the most recent absorbed fault (None until
        # one happens): isolation keeps serving, launchers report the cause
        self.last_fault: Optional[BaseException] = None

        cfg_, ctx_ = self.cfg, self.ctx
        paged = self.paged
        scfg, base_key = self.sampling, smp.base_key(self.sampling)
        decode_steps = self.scheduler.cfg.decode_steps

        def _row(table, slot):
            # one compiled executable serves every slot: the slot's table
            # row is sliced with a traced index
            return jax.lax.dynamic_slice(table, (slot, 0),
                                         (1, table.shape[1]))

        def _reset(pool, slot, template, pos0):
            return sp.reset_slot(pool, slot, template, pos0, paged)

        def _prefill(params, pool, table, slot, chunk, window):
            st = sp.gather_slot(pool, slot, paged)
            if paged:
                st = dict(st, pages=_row(table, slot))
            # route="prefill": every chunk — the 1-token tail included —
            # takes the backend prefill_attention primitive, the same
            # primitive serial whole-prompt prefill resolves to, so chunked
            # and whole-prompt prefill share bit-identical numerics on
            # every backend (the route enum makes the old fragile
            # "tail chunk must pass decode=False" contract unexpressible)
            logits, new = lm.decode_step(params, cfg_, st, chunk, ctx_,
                                         window=window, route="prefill")
            return logits[:, -1], sp.scatter_slot(pool, slot, new, paged)

        def _spec_prefill(dparams, vparams, dpool, vpool, table, slot,
                          chunk, window):
            # speculative mode prefills BOTH pools from one dispatch (the
            # drafter's chunk logits are never consumed — the first token
            # always comes from the verifier); fusing halves the per-chunk
            # dispatch overhead vs two _prefill_fn calls
            pg = (dict(pages=_row(table, slot)) if paged else {})
            vst = dict(sp.gather_slot(vpool, slot, paged), **pg)
            vlogits, vnew = lm.decode_step(vparams, cfg_, vst, chunk, ctx_,
                                           window=window, route="prefill")
            dst = dict(sp.gather_slot(dpool, slot, paged), **pg)
            _, dnew = lm.decode_step(dparams, cfg_, dst, chunk, ctx_,
                                     window=window, route="prefill")
            return (vlogits[:, -1],
                    sp.scatter_slot(dpool, slot, dnew, paged),
                    sp.scatter_slot(vpool, slot, vnew, paged))

        def _decode(params, pool, table, tokens, active, eos, budget,
                    window):
            """``decode_steps`` greedy steps on device. tokens (B, 1) i32 =
            each live slot's last emitted token; active (B,) bool; eos (B,)
            i32 (-1 = no EOS id); budget (B,) i32 = tokens the slot may
            still emit. Returns (toks (K, B), emitted (K, B) bool, pool):
            ``emitted[t, i]`` marks a real token — slots that hit EOS or
            exhaust their budget mid-scan are frozen (select-masked) for the
            remaining steps, exactly as the host's eviction logic would."""
            def body(carry, _):
                pool, tok, live, left = carry
                st = dict(pool, pages=table) if paged else pool
                logits, new = lm.decode_step(params, cfg_, st, tok, ctx_,
                                             window=window, route="decode")
                # per-slot key derives from the sampled token's absolute
                # position (new pos), never slot/tick — so engine sampling
                # reproduces serial sampling token-for-token per seed;
                # greedy is a static argmax branch (no keys, bit-identical
                # to the pre-sampling engine)
                nxt = smp.sample_batch(logits[:, -1], scfg, base_key,
                                       new["pos"])
                pool = sp.select_slots(new, pool, live, paged)
                left = jnp.where(live, left - 1, left)
                stop = ((eos >= 0) & (nxt == eos)) | (left <= 0)
                return ((pool, jnp.where(live, nxt, tok[:, 0])[:, None],
                         live & ~stop, left),
                        (jnp.where(live, nxt, 0), live))

            (pool, _, _, _), (toks, emitted) = jax.lax.scan(
                body, (pool, tokens, active, budget), None,
                length=decode_steps)
            return toks, emitted, pool

        def _copy_page(pool, dpool, src, dst):
            # copy-on-write: duplicate arena page src -> dst in every KV
            # entry of both pools (dpool is None outside speculative mode;
            # the page axis of an arena leaf is axis 1, under the group
            # stack)
            def cp(leaf):
                page = jax.lax.dynamic_slice_in_dim(leaf, src, 1, 1)
                return jax.lax.dynamic_update_slice_in_dim(leaf, page,
                                                           dst, 1)
            def one(pool):
                caches = tuple(
                    jax.tree.map(cp, e) if sp.is_kv_entry(e) else e
                    for e in pool["caches"])
                return {"caches": caches, "pos": pool["pos"]}
            return one(pool), (None if dpool is None else one(dpool))

        # every hot path declares its compiled-artifact invariants next to
        # its jit (DESIGN.md §15): scripts/check_static.py lowers these with
        # representative shapes and walks the optimized HLO to enforce the
        # claims. n_windows is the window-bucketing retrace bound: static
        # windows are window_block multiples, so steady-state serving
        # compiles at most max_seq/window_block decode variants (prefill
        # additionally varies over the <= prefill_chunk tail-chunk widths).
        n_windows = -(-max_seq // self.scheduler.cfg.window_block)
        n_chunks = self.scheduler.cfg.prefill_chunk
        self._reset_fn = declare_invariants(
            "engine.reset", host_syncs=1, donated=("pool",),
            forbid_f32_roundtrip_on=("kv",),
            max_lowerings=2 if self.spec is not None else 1,
        )(jax.jit(_reset, donate_argnums=(0,)))
        self._prefill_fn = declare_invariants(
            "engine.prefill", host_syncs=1, donated=("pool",),
            forbid_f32_roundtrip_on=("kv",),
            max_lowerings=n_windows * n_chunks, static_argnums=(5,),
        )(jax.jit(_prefill, donate_argnums=(1,), static_argnums=(5,)))
        self._decode_fn = declare_invariants(
            "engine.decode", host_syncs=1, donated=("pool",),
            forbid_f32_roundtrip_on=("kv",),
            max_lowerings=n_windows, static_argnums=(7,),
        )(jax.jit(_decode, donate_argnums=(1,), static_argnums=(7,)))
        self._spec_prefill_fn = declare_invariants(
            "engine.spec_prefill", host_syncs=1, donated=("dpool", "vpool"),
            forbid_f32_roundtrip_on=("kv",),
            max_lowerings=n_windows * n_chunks, static_argnums=(7,),
        )(jax.jit(_spec_prefill, donate_argnums=(2, 3), static_argnums=(7,)))
        self._copy_page_fn = declare_invariants(
            "engine.copy_page", host_syncs=1, donated=("pool", "dpool"),
            forbid_f32_roundtrip_on=("kv",),
        )(jax.jit(_copy_page, donate_argnums=(0, 1)))
        self._sample_fn = jax.jit(lambda lg, p: smp.sample(
            lg, scfg, smp.token_key(base_key, p)))

    def _first_token(self, logits_row, pos: int) -> int:
        """Token emitted from a prefill tail chunk's last-position logits.
        ``pos`` is the prompt length; see ``_pick_token`` for the key rule."""
        return _pick_token(logits_row, pos,
                           None if self.sampling.is_greedy
                           else self._sample_fn)

    # ------------------------------------------------------------ paged KV
    def _note_pages(self) -> None:
        n = self.alloc.pages_in_use
        self.stats["pages_in_use"] = n
        if n > self.stats["pages_peak"]:
            self.stats["pages_peak"] = n
            self.stats["kv_bytes_peak"] = n * self._kv_page_bytes

    def _alloc_pages(self, n: int) -> List[int]:
        """Allocate n pages, evicting prefix-cache LRU entries under arena
        pressure; raises MemoryError only once the cache is drained."""
        if n <= 0:
            return []
        while True:
            try:
                return self.alloc.alloc(n)
            except MemoryError:
                if self.prefix is None or not self.prefix.evict_lru():
                    raise

    def _map_slot_pages(self, slot: _Slot, prompt: np.ndarray) -> int:
        """Admission: map the slot's page-table row for ``prompt`` — the
        longest page-aligned prefix-cache hit (copy-free, refcounted) plus
        fresh pages for the rest of the prompt. Returns the hit length in
        tokens (the position prefill resumes from)."""
        hit, pages = ((0, []) if self.prefix is None
                      else self.prefix.lookup(prompt))
        try:
            pages = pages + self._alloc_pages(
                page_count(prompt.size, self.page_size) - len(pages))
        except MemoryError:
            # lookup() ref'd the hit pages for this slot; the mapping
            # failed, so drop those references or they leak forever
            if pages:
                self.alloc.unref(pages)
            raise
        slot.pages = pages
        slot.n_shared = hit // self.page_size
        self.table[slot.idx] = 0
        self.table[slot.idx, :len(pages)] = pages
        self._table_cache.clear()
        if hit:
            self.stats["prefix_hits"] += 1
            self.stats["prefix_hit_tokens"] += hit
            self.stats["bytes_saved"] += hit * self._kv_token_bytes
        self._note_pages()
        return hit

    def _ensure_capacity(self, slot: _Slot, upto: int) -> None:
        """Grow the slot's table to cover writes at positions < ``upto``
        BEFORE the dispatch: a write through an unmapped (zero) table entry
        would land on the trash page and silently lose that KV."""
        need = page_count(min(upto, self.max_seq), self.page_size)
        if need > len(slot.pages):
            new = self._alloc_pages(need - len(slot.pages))
            self.table[slot.idx, len(slot.pages):need] = new
            slot.pages.extend(new)
            self._table_cache.clear()
            self._note_pages()

    def _ensure_writable(self, slot: _Slot, pos: int) -> None:
        """Copy-on-write ahead of a dispatch whose first KV write lands at
        ``pos``: if that position sits inside the slot's shared-page range
        (only the speculative healing chunk — writing at pos-1 — can reach
        it, when the prompt length is page-aligned and its last page went
        into the prefix cache), the page is duplicated and the table
        repointed so sharers never observe the write."""
        if pos < 0 or pos >= slot.n_shared * self.page_size:
            return
        idx = pos // self.page_size        # == n_shared - 1: writes only
        old = slot.pages[idx]              # ever touch the LAST shared page
        if self.alloc.refs[old] > 1:
            new = self._alloc_pages(1)[0]
            self.pool, dpool = self._copy_page_fn(
                self.pool,
                self.draft_pool if self.spec is not None else None,
                jnp.int32(old), jnp.int32(new))
            if dpool is not None:
                self.draft_pool = dpool
            self.alloc.unref([old])
            slot.pages[idx] = new
            self.table[slot.idx, idx] = new
            self._table_cache.clear()
            self.stats["cow_copies"] += 1
        slot.n_shared = idx                # earlier pages are never written
        self._note_pages()

    def _release_slot_pages(self, slot: _Slot) -> None:
        """Eviction: drop the slot's page references (pages the prefix
        cache also holds stay resident for future hits) and zero its table
        row."""
        if slot.pages:
            self.alloc.unref(slot.pages)
            slot.pages = []
            slot.n_shared = 0
            self.table[slot.idx] = 0
            self._table_cache.clear()
            self._note_pages()

    def _dispatch_table(self, active: Optional[np.ndarray] = None):
        """Device copy of the page table for one dispatch. Batched decode
        dispatches pass ``active`` to redirect every inactive slot's row to
        the trash page: the shared arena cannot be select-masked per slot,
        so inactive rows' garbage writes are steered to the reserved page
        instead (their live pages are never addressed at all).

        The device copy is cached per (table state, active mask): the table
        only mutates on admit / growth / CoW / eviction, so steady-state
        decode ticks reuse one resident array instead of paying an H2D
        upload per dispatch (none of the jitted callables donate the table
        argument, so the cached buffer stays live)."""
        key = (active.tobytes()
               if active is not None and self.paged else None)
        dev = self._table_cache.get(key)
        if dev is None:
            tab = self.table
            if key is not None:
                tab = np.where(active[:, None], tab, 0)
            dev = self._table_cache[key] = jnp.asarray(tab)
        return dev

    def _window(self, needed: int) -> int:
        if self.paged:
            return self.scheduler.visible_window(
                needed, self.max_seq, page_multiple=self.page_size)
        return self.scheduler.visible_window(needed, self.max_seq)

    # ------------------------------------------------------------- lifecycle
    def submit(self, request: Request) -> int:
        prompt = np.asarray(request.prompt, np.int32)
        if prompt.ndim != 1 or prompt.size == 0:
            raise ValueError("prompt must be a non-empty 1-D token list")
        if request.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1 (the first token "
                             "falls out of prefill unconditionally)")
        if prompt.size + request.max_new_tokens > self.max_seq:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens "
                f"({request.max_new_tokens}) exceeds max_seq={self.max_seq}")
        # identity is always engine-assigned: a caller-supplied Request.uid
        # could collide with the internal counter and alias two requests
        uid = next(self._uid)
        req = dataclasses.replace(request, uid=uid, prompt=prompt)
        req._t_submit = self.clock()       # type: ignore[attr-defined]
        if self.tracer is not None:
            self.tracer.submit(uid, req._t_submit, int(prompt.size))
        self.waiting.append(req)
        return uid

    def cancel(self, uid: int) -> bool:
        """Abort a request wherever it currently lives (the service layer's
        deadline-eviction hook). A queued request is dropped from the waiting
        list; an in-flight one — mid-prefill included — has its slot freed
        immediately and, in paged mode, its page references released (pages
        the prefix cache also holds stay resident for future hits). The
        slot's device state needs no scrubbing: a freed slot's stale KV is
        masked by ``pos`` on the next admission, exactly as on normal
        eviction. Returns False when the uid is unknown or already
        finished."""
        for i, req in enumerate(self.waiting):
            if req.uid == uid:
                del self.waiting[i]
                self.stats["cancelled"] += 1
                if self.tracer is not None:
                    self.tracer.finish(uid, self.clock(), "cancelled")
                return True
        for slot in self.slots:
            if slot.stage != FREE and slot.result is not None \
                    and slot.result.uid == uid:
                if self.tracer is not None:
                    self.tracer.finish(uid, self.clock(), "cancelled",
                                       n_tokens=len(slot.result.tokens),
                                       pages_held=len(slot.pages))
                slot.stage = FREE
                slot.result = None
                slot.prompt = None
                if self.paged:
                    self._release_slot_pages(slot)
                self.stats["cancelled"] += 1
                return True
        return False

    @property
    def has_work(self) -> bool:
        return bool(self.waiting) or any(s.stage != FREE for s in self.slots)

    @property
    def n_active(self) -> int:
        return sum(s.stage != FREE for s in self.slots)

    def _admit(self) -> None:
        for slot in self.slots:
            if not self.waiting:
                return
            if slot.stage != FREE:
                continue
            req = self.waiting.pop(0)
            self._fault_phase = ("admit", req)
            pos0 = (self._map_slot_pages(slot, req.prompt) if self.paged
                    else 0)
            self.pool = self._reset_fn(self.pool, jnp.int32(slot.idx),
                                       self._template, jnp.int32(pos0))
            if self.spec is not None:
                self.draft_pool = self._reset_fn(
                    self.draft_pool, jnp.int32(slot.idx),
                    self._draft_template, jnp.int32(pos0))
            slot.stage = PREFILL
            slot.prompt = req.prompt
            slot.prefill_done = pos0
            slot.eos_id = req.eos_id
            slot.max_new_tokens = req.max_new_tokens
            t_admit = self.clock()
            slot.result = RequestResult(
                uid=req.uid, prompt_len=int(req.prompt.size), tokens=[],
                finish_reason="", t_submit=req._t_submit,
                t_admit=t_admit)
            if self.tracer is not None:
                self.tracer.admit(req.uid, t_admit, slot.idx)
            self._fault_phase = None

    def _emit(self, slot: _Slot, tok: int,
              finished: List[RequestResult]) -> None:
        res = slot.result
        if not res.tokens:
            res.t_first_token = self.clock()
            if self.tracer is not None:
                self.tracer.first_token(res.uid, res.t_first_token)
        res.tokens.append(tok)
        if self.on_token is not None:
            self.on_token(res.uid, tok)
        done_eos = slot.eos_id is not None and tok == slot.eos_id
        done_len = len(res.tokens) >= slot.max_new_tokens
        if done_eos or done_len:
            res.finish_reason = "eos" if done_eos else "length"
            res.t_finish = self.clock()
            if self.tracer is not None:
                self.tracer.finish(res.uid, res.t_finish, res.finish_reason,
                                   n_tokens=len(res.tokens),
                                   pages_held=len(slot.pages))
            finished.append(res)
            slot.stage = FREE          # eviction: slot reusable next tick
            slot.result = None
            slot.prompt = None
            if self.paged:
                self._release_slot_pages(slot)
        else:
            slot.last_token = tok
            slot.stage = DECODE

    # ------------------------------------------------------------------ step
    def _slot_pos(self, slot: _Slot) -> int:
        """Cache position the slot's next decode step writes at (the engine's
        host-side mirror of ``pool["pos"][slot.idx]``): the whole prompt plus
        every emitted token except the newest (whose KV isn't written yet)."""
        return int(slot.prompt.size) + len(slot.result.tokens) - 1

    def _debug_check_window(self, window: int, required: int,
                            kind: str) -> None:
        """Opt-in (``REPRO_DEBUG_WINDOW=1``) host-side guard on the static
        visible window, run before dispatch. An undersized window —
        ``window < start + Sq`` for a consumed row — does NOT error on
        device: the attend silently truncates the visible cache and the
        engine emits wrong tokens. This assert turns that silent corruption
        into an immediate host error; it is opt-in because it runs on every
        dispatch in the hot loop."""
        if os.environ.get("REPRO_DEBUG_WINDOW") != "1":
            return
        if window < min(required, self.max_seq):
            raise AssertionError(
                f"undersized visible window on {kind} dispatch: window="
                f"{window} < required={min(required, self.max_seq)} — the "
                f"attend would silently truncate the cache and emit wrong "
                f"tokens (scheduler.visible_window miscomputed?)")

    def step(self) -> List[RequestResult]:
        """One engine tick: admit, then run one scheduler action (a decode
        action runs ``decode_steps`` device steps). Returns requests that
        finished this tick.

        REQUEST-SCOPED FAULT ISOLATION: an exception inside the tick is
        absorbed — the requests the failing phase was working on (the
        admission's request; the prefill slot; a decode dispatch's batch)
        finish with ``finish_reason="error"``, their slots and pages are
        freed, ``stats["faults"]`` counts them, and the engine keeps
        serving everything else.  Two kinds propagate to the caller
        instead: ``AssertionError`` (invariant checks like the
        REPRO_DEBUG_WINDOW guard or allocator refcount asserts — those
        are engine bugs, and blaming the request they happened to fire
        on would hide them), and any fault the engine cannot attribute
        to requests (``_fault_phase`` unset).

        Every step leaves its measurement behind in ``last_step``:
        wall time, the per-phase breakdown (admit / prefill dispatch /
        decode scan / host sync / token fanout), and the step's
        prefill/decode token deltas — the single source the service
        layer feeds to both the admission EWMA and the phase
        histograms."""
        self._fault_phase = None
        self._ph = {}
        p0 = self.stats["prefill_tokens"]
        a0 = self.stats["accepted_tokens"]
        t0 = self.clock()
        try:
            out = self._step_inner()
        except AssertionError:
            raise
        except Exception as e:
            self.last_fault = e
            out = self._absorb_fault()
        wall = self.clock() - t0
        self._ph["total"] = wall
        self.last_step = {
            "wall_s": wall,
            "phases": self._ph,
            "prefill_tokens": self.stats["prefill_tokens"] - p0,
            "decode_tokens": self.stats["accepted_tokens"] - a0,
        }
        if self.tracer is not None:
            self.tracer.span("step", None, t0, t0 + wall,
                             **{k: round(v, 9)
                                for k, v in self._ph.items()})
        return out

    def _step_inner(self) -> List[RequestResult]:
        clk = self.clock
        ph = self._ph
        t_in = clk()
        self._admit()
        ph["admit"] = clk() - t_in
        prefilling = [s.idx for s in self.slots if s.stage == PREFILL]
        decoding = [s.idx for s in self.slots if s.stage == DECODE]
        action = self.scheduler.next_action(prefilling, decoding)
        finished: List[RequestResult] = []

        if action.kind == PREFILL:
            slot = self.slots[action.slot]
            uid = slot.result.uid
            self._fault_phase = ("slots", [action.slot])
            lo, hi = self.scheduler.chunk_bounds(slot.prompt.size,
                                                 slot.prefill_done)
            chunk = jnp.asarray(slot.prompt[None, lo:hi])
            window = self._window(hi)
            # the chunk's last query sits at absolute position hi-1
            self._debug_check_window(window, hi, "prefill")
            table = self._dispatch_table()
            t_d0 = clk()
            if self.spec is not None:
                last_logits, self.draft_pool, self.pool = \
                    self._spec_prefill_fn(
                        self.spec.draft_params, self.params, self.draft_pool,
                        self.pool, table, jnp.int32(slot.idx), chunk, window)
            else:
                last_logits, self.pool = self._prefill_fn(
                    self.params, self.pool, table, jnp.int32(slot.idx),
                    chunk, window)
            t_d1 = clk()
            ph["prefill_dispatch"] = t_d1 - t_d0
            slot.prefill_done = hi
            self.stats["prefill_ticks"] += 1
            self.stats["prefill_tokens"] += hi - lo
            emitted_tail = 0
            if hi == slot.prompt.size:
                if self.paged and self.prefix is not None:
                    # the prompt's KV is complete: register every page-
                    # aligned prefix for future admissions. The slot's own
                    # pages up to the inserted length are now shared —
                    # future in-place writes there must copy first.
                    ins = self.prefix.insert(slot.prompt, slot.pages, hi)
                    slot.n_shared = max(slot.n_shared,
                                        ins // self.page_size)
                    self._note_pages()
                tok = self._first_token(last_logits[0], hi)
                t_s1 = clk()
                ph["host_sync"] = t_s1 - t_d1
                self.stats["host_syncs"] += 1
                # the speculative healing chunk re-feeds [prev, last]: after
                # prefill, pos-1 holds the last prompt token
                slot.prev_token = int(slot.prompt[-1])
                emitted_tail = 1
                # span before the tail _emit: a max_new_tokens=1 request
                # finishes inside it, and its finish instant must account
                # for this chunk's token
                if self.tracer is not None:
                    self.tracer.span("prefill", uid, t_d0, t_s1,
                                     lo=lo, hi=hi, tokens=emitted_tail)
                self._emit(slot, tok, finished)
                ph["token_fanout"] = clk() - t_s1
            elif self.tracer is not None:
                self.tracer.span("prefill", uid, t_d0, clk(),
                                 lo=lo, hi=hi, tokens=emitted_tail)
        elif action.kind == DECODE and self.spec is not None:
            finished = self._spec_decode(action, finished)
        elif action.kind == DECODE:
            k_steps = self.scheduler.cfg.decode_steps
            t_d0 = clk()
            tokens = np.zeros((self.n_slots, 1), np.int32)
            active = np.zeros((self.n_slots,), bool)
            eos = np.full((self.n_slots,), -1, np.int32)
            budget = np.ones((self.n_slots,), np.int32)
            for i in action.slots:
                slot = self.slots[i]
                # capacity growth can exhaust the arena — blame only the
                # slot being grown, not the whole dispatch batch
                self._fault_phase = ("slots", [i])
                tokens[i, 0] = slot.last_token
                active[i] = True
                if slot.eos_id is not None:
                    eos[i] = slot.eos_id
                budget[i] = slot.max_new_tokens - len(slot.result.tokens)
                if self.paged:
                    # deepest write this dispatch: pos + live steps (frozen
                    # slots rewrite their freeze position, already covered)
                    self._ensure_capacity(
                        slot, min(self._slot_pos(slot) + k_steps,
                                  int(slot.prompt.size)
                                  + slot.max_new_tokens))
            # past here a fault hits the batched dispatch itself: every
            # slot in the action is the blast radius
            self._fault_phase = ("slots", list(action.slots))
            # the deepest live slot after k_steps attends positions
            # <= max(pos) + k_steps - 1  ->  window covers max(pos) + k_steps
            needed = max(self._slot_pos(self.slots[i])
                         for i in action.slots) + k_steps
            window = self._window(needed)
            self._debug_check_window(window, needed, "decode")
            toks, emitted, self.pool = self._decode_fn(
                self.params, self.pool, self._dispatch_table(active),
                jnp.asarray(tokens), jnp.asarray(active), jnp.asarray(eos),
                jnp.asarray(budget), window)
            t_d1 = clk()
            ph["decode_scan"] = t_d1 - t_d0
            toks, emitted = np.asarray(toks), np.asarray(emitted)
            t_s1 = clk()
            ph["host_sync"] = t_s1 - t_d1
            self.stats["host_syncs"] += 1
            self.stats["device_steps"] += k_steps
            # every slot live at dispatch burns all k_steps device steps —
            # slots that freeze mid-scan included (the previously
            # under-counted device work); emitted is what actually landed
            self.stats["drafted_tokens"] += k_steps * len(action.slots)
            self.stats["accepted_tokens"] += int(emitted.sum())
            # scan spans are recorded BEFORE fanout: _emit fires terminal
            # finish instants, and the finish must account for every token
            # its work spans carry (the trace smoke asserts this). The span
            # therefore covers dispatch..host-sync; fanout is engine-side
            # bookkeeping attributed to the step track.
            if self.tracer is not None:
                per_slot = emitted.sum(axis=0)
                for i in action.slots:
                    self.tracer.span("decode", self.slots[i].result.uid,
                                     t_d0, t_s1, tokens=int(per_slot[i]),
                                     k_steps=k_steps)
            for t in range(k_steps):
                for i in action.slots:
                    if emitted[t, i]:
                        self._emit(self.slots[i], int(toks[t, i]), finished)
            t_f1 = clk()
            ph["token_fanout"] = t_f1 - t_s1
            self.stats["decode_ticks"] += 1
            self.stats["decode_slot_steps"] += int(emitted.sum())

        self.ticks += 1
        return finished

    # ------------------------------------------------------- fault isolation
    def _fail_slot(self, slot: _Slot, finished: List[RequestResult],
                   now: float) -> None:
        """Evict a faulted slot: its result finishes with
        ``finish_reason="error"``, its pages are freed, the slot is
        immediately reusable."""
        res = slot.result
        if res is not None:
            res.finish_reason = "error"
            if not res.t_first_token:
                res.t_first_token = now
            res.t_finish = now
            if self.tracer is not None:
                self.tracer.finish(res.uid, now, "error",
                                   n_tokens=len(res.tokens),
                                   pages_held=len(slot.pages))
            finished.append(res)
        slot.stage = FREE
        slot.result = None
        slot.prompt = None
        if self.paged:
            self._release_slot_pages(slot)
        self.stats["faults"] += 1

    def _pool_deleted(self) -> bool:
        """True when a fault fired mid-execution of a donating dispatch:
        the donated input buffers are consumed but the output never
        materialized — the pool is gone and must be rebuilt."""
        leaves = jax.tree_util.tree_leaves(self.pool)
        if self.spec is not None:
            leaves += jax.tree_util.tree_leaves(self.draft_pool)
        return any(getattr(leaf, "is_deleted", lambda: False)()
                   for leaf in leaves)

    def _rebuild_pools(self) -> None:
        """Re-initialize the state pool(s) after donation consumed them.
        Every slot's KV is lost, so the caller fails all active slots
        first; cached prefix pages hold vanished KV too and must go."""
        if self.paged:
            if self.prefix is not None:
                self.prefix.clear()
            self.pool = sp.init_paged_pool(
                self.cfg, self.n_slots, self.max_seq, self.ctx,
                params=self.params, page_size=self.page_size,
                total_pages=self.total_pages)
        else:
            self.pool = sp.init_pool(self.cfg, self.n_slots, self.max_seq,
                                     self.ctx, params=self.params)
        if self.spec is not None:
            dctx = self.spec.draft_ctx
            if self.paged:
                self.draft_pool = sp.init_paged_pool(
                    self.cfg, self.n_slots, self.max_seq, dctx,
                    params=self.spec.draft_params, page_size=self.page_size,
                    total_pages=self.total_pages)
            else:
                self.draft_pool = sp.init_pool(
                    self.cfg, self.n_slots, self.max_seq, dctx,
                    params=self.spec.draft_params)
        self._table_cache.clear()

    def _absorb_fault(self) -> List[RequestResult]:
        """Exception handler for one tick (called from ``step``'s except
        block; re-raises when the fault is unattributable). Returns the
        error-finished results so the service can route them."""
        phase = self._fault_phase
        self._fault_phase = None
        if phase is None:
            raise          # no request to blame: let the caller see it
        now = self.clock()
        finished: List[RequestResult] = []
        kind, who = phase
        pool_dead = self._pool_deleted()
        if kind == "admit":
            # the request was popped from waiting but its slot never went
            # live — synthesize its error result directly
            req = who
            self.stats["faults"] += 1
            if self.tracer is not None:
                self.tracer.finish(req.uid, now, "error")
            finished.append(RequestResult(
                uid=req.uid, prompt_len=int(req.prompt.size), tokens=[],
                finish_reason="error", t_submit=req._t_submit, t_admit=now,
                t_first_token=now, t_finish=now))
        else:
            for i in who:
                if self.slots[i].stage != FREE:
                    self._fail_slot(self.slots[i], finished, now)
        if pool_dead:
            # the dispatch consumed its donated pool before dying: every
            # active slot's KV went with it — fail them all and rebuild
            for slot in self.slots:
                if slot.stage != FREE:
                    self._fail_slot(slot, finished, now)
            self._rebuild_pools()
        self.ticks += 1
        return finished

    def _spec_decode(self, action, finished: List[RequestResult]
                     ) -> List[RequestResult]:
        """``c_eff`` speculative cycles over all decoding slots — k_eff
        draft steps on the drafter pool, one multi-position verify on the
        verifier pool, on-device acceptance + rollback each — with ONE host
        sync at the end. ``SpecDecoder.plan`` caps (k, cycles) so the
        deepest slot's verify writes stay inside the cache (the vmapped KV
        scatter clamps out-of-range starts, which would corrupt valid
        history)."""
        clk = self.clock
        ph = self._ph
        t_d0 = clk()
        prev = np.zeros((self.n_slots, 1), np.int32)
        tokens = np.zeros((self.n_slots, 1), np.int32)
        active = np.zeros((self.n_slots,), bool)
        eos = np.full((self.n_slots,), -1, np.int32)
        budget = np.ones((self.n_slots,), np.int32)
        for i in action.slots:
            slot = self.slots[i]
            prev[i, 0] = slot.prev_token
            tokens[i, 0] = slot.last_token
            active[i] = True
            if slot.eos_id is not None:
                eos[i] = slot.eos_id
            budget[i] = slot.max_new_tokens - len(slot.result.tokens)
        max_pos = max(self._slot_pos(self.slots[i]) for i in action.slots)
        k_eff, c_eff = self.spec.plan(max_pos, self.max_seq,
                                      int(budget[active].max()))
        if self.paged:
            for i in action.slots:
                slot = self.slots[i]
                self._fault_phase = ("slots", [i])
                # the healing chunk's first write lands at pos-1 — possibly
                # inside a shared page (copy-on-write); the verify tail is
                # the deepest write (plan() keeps it in-bounds)
                self._ensure_writable(slot, self._slot_pos(slot) - 1)
                self._ensure_capacity(
                    slot, self._slot_pos(slot) + c_eff * (k_eff + 1))
        self._fault_phase = ("slots", list(action.slots))
        # deepest attend: the last cycle's verify chunk tail
        needed = max_pos + c_eff * (k_eff + 1)
        window = self._window(needed)
        self._debug_check_window(window, needed, "speculative")
        toks, emitted, n_acc, n_drafted, self.draft_pool, self.pool = \
            self.spec.spec_fn(
                self.spec.draft_params, self.params, self.draft_pool,
                self.pool, self._dispatch_table(active), jnp.asarray(prev),
                jnp.asarray(tokens), jnp.asarray(active), jnp.asarray(eos),
                jnp.asarray(budget), k_eff, c_eff, window)
        t_d1 = clk()
        ph["decode_scan"] = t_d1 - t_d0
        toks, emitted = np.asarray(toks), np.asarray(emitted)
        n_acc, n_drafted = np.asarray(n_acc), np.asarray(n_drafted)
        t_s1 = clk()
        ph["host_sync"] = t_s1 - t_d1
        self.stats["host_syncs"] += 1
        # k_eff drafter invocations (healing chunk included) + 1 verify
        # per cycle
        self.stats["device_steps"] += c_eff * (k_eff + 1)
        self.stats["drafted_tokens"] += int(n_drafted.sum())
        self.stats["accepted_tokens"] += int(n_acc.sum())
        # span before fanout: _emit fires terminal finish instants, and the
        # finish must account for every token its work spans carry (span
        # covers dispatch..host-sync; fanout is the step track's phase)
        if self.tracer is not None:
            per_slot = emitted.sum(axis=0)
            for i in action.slots:
                self.tracer.span("spec", self.slots[i].result.uid,
                                 t_d0, t_s1, tokens=int(per_slot[i]),
                                 drafted=int(n_drafted[i]),
                                 accepted=int(n_acc[i]),
                                 k=k_eff, cycles=c_eff)
        # nonzero is row-major (t ascending), so per-slot emission order is
        # preserved without scanning all c*(k+1) x n_slots cells in Python
        for t, i in zip(*np.nonzero(emitted)):
            slot = self.slots[i]
            slot.prev_token = slot.last_token
            self._emit(slot, int(toks[t, i]), finished)
        t_f1 = clk()
        ph["token_fanout"] = t_f1 - t_s1
        self.stats["decode_ticks"] += 1
        self.stats["decode_slot_steps"] += int(emitted.sum())
        return finished

    # ------------------------------------------------------------------- run
    def run(self, requests: Sequence[Request],
            arrivals_s: Optional[Sequence[float]] = None,
            arrival_ticks: Optional[Sequence[int]] = None,
            ) -> Dict[int, RequestResult]:
        """Drive the given requests to completion; returns results keyed by
        the request's INDEX in ``requests`` (uids are engine-internal).

        ``arrivals_s``: wall-clock offsets (trace replay);
        ``arrival_ticks``: deterministic engine-tick offsets (tests). With
        neither, everything is submitted up front."""
        if arrivals_s is not None and arrival_ticks is not None:
            raise ValueError("pass at most one of arrivals_s/arrival_ticks")
        if self.has_work:
            raise RuntimeError(
                "run() requires an idle engine: requests already queued via "
                "submit() have no index in this run's result map — drain "
                "them with step() first")
        offsets = (arrivals_s if arrivals_s is not None else arrival_ticks
                   if arrival_ticks is not None else [0] * len(requests))
        pending = sorted(zip(offsets, range(len(requests))), key=lambda p: p[0])
        by_wall = arrivals_s is not None
        t0 = self.clock()
        tick0 = self.ticks          # offsets are relative to THIS run's start
        uid_to_index: Dict[int, int] = {}
        results: Dict[int, RequestResult] = {}
        while pending or self.has_work:
            now = (self.clock() - t0) if by_wall else self.ticks - tick0
            while pending and pending[0][0] <= now:
                _, i = pending.pop(0)
                uid_to_index[self.submit(requests[i])] = i
            if self.has_work:
                for res in self.step():
                    results[uid_to_index[res.uid]] = res
            elif pending:
                if by_wall:
                    # idle engine: sleep until the next arrival is actually
                    # due (a fixed cap here was a 1 ms busy-wait per loop)
                    time.sleep(max(0.0, pending[0][0] - now))
                else:
                    self.ticks += 1     # idle tick until the next arrival
        return results


# ------------------------------------------------------------------- stats
def latency_histogram(values_s: Sequence[float]) -> Dict[str, Any]:
    """Seconds -> the shared fixed-bucket latency histogram (JSON form);
    every latency/TTFT distribution in BENCH_serving.json uses these
    buckets so bench_diff can compare shapes across baselines."""
    h = telemetry.Histogram("latency_s",
                            buckets=telemetry.schema.LATENCY_BUCKETS_S)
    for v in values_s:
        h.observe(v)
    return h.to_dict()


def summarize_results(results: Dict[int, RequestResult],
                      wall_s: float) -> Dict[str, Any]:
    """Throughput + nearest-rank latency/TTFT percentiles over a finished
    result set (shared by `serve --engine` and the serving bench), plus
    the full latency/TTFT distributions as fixed-bucket histograms. An
    empty result set (a bench variant whose requests all failed admission,
    or a zero-request trace) yields a zeroed summary instead of an
    IndexError from the nearest-rank lookup."""
    if not results:
        return {"n_requests": 0, "out_tokens": 0, "tokens_per_s": 0.0,
                "latency_p50_ms": 0.0, "latency_p95_ms": 0.0,
                "ttft_p50_ms": 0.0, "ttft_p95_ms": 0.0,
                "latency_hist": latency_histogram(()),
                "ttft_hist": latency_histogram(())}
    lat = sorted(r.latency_s for r in results.values())
    ttft = sorted(r.ttft_s for r in results.values())

    def pct(xs, q):
        return xs[max(0, -(-int(q * len(xs)) // 100) - 1)]

    out_tokens = sum(len(r.tokens) for r in results.values())
    return {
        "n_requests": len(results),
        "out_tokens": out_tokens,
        "tokens_per_s": out_tokens / max(wall_s, 1e-9),
        "latency_p50_ms": pct(lat, 50) * 1e3,
        "latency_p95_ms": pct(lat, 95) * 1e3,
        "ttft_p50_ms": pct(ttft, 50) * 1e3,
        "ttft_p95_ms": pct(ttft, 95) * 1e3,
        "latency_hist": latency_histogram(lat),
        "ttft_hist": latency_histogram(ttft),
    }


# ---------------------------------------------------------------- reference
@functools.lru_cache(maxsize=8)
def _serial_step(cfg, ctx):
    """One jitted decode step per (cfg, ctx) — serial_decode is called once
    per verified request, and a fresh jit(lambda) per call would recompile
    the (1, 1) decode graph every time."""
    return jax.jit(lambda p, st, t: lm.decode_step(p, cfg, st, t, ctx))


@functools.lru_cache(maxsize=8)
def _serial_sampler(scfg: smp.SamplingConfig):
    """Jitted (logits, pos) -> token for one SamplingConfig — the SAME key
    rule (seed x absolute position) the engine's batched scan uses, so a
    fixed seed yields identical tokens serial vs engine."""
    base = smp.base_key(scfg)
    return jax.jit(lambda lg, p: smp.sample(lg, scfg, smp.token_key(base, p)))


def serial_decode(params, cfg, prompt: Sequence[int], max_new_tokens: int,
                  ctx: Optional[RunContext] = None, max_seq: int = 128,
                  eos_id: Optional[int] = None,
                  sampling: Optional[smp.SamplingConfig] = None,
                  decode_rows: int = 1) -> List[int]:
    """The serial single-request path the engine must match token-for-token:
    whole-prompt prefill, then one decode step per token. Greedy by default;
    a non-greedy ``sampling`` draws each token with the shared
    position-derived key rule.

    ``decode_rows``: the decode steps run the request replicated over this
    many batch rows and read row 0. XLA on TPU is not batch-invariant — a
    one-row program can round differently from a four-row one (its fusions
    differ) — so the reference for an engine with ``n_slots`` slots decodes
    at that width, exactly as the engine's batched decode does (prefill is
    one row in both)."""
    ctx = ctx or default_ctx()
    scfg = sampling or smp.GREEDY
    prompt = np.asarray(prompt, np.int32)
    state = lm.init_decode_state(cfg, 1, max_seq, ctx, params=params)
    step = _serial_step(cfg, ctx)
    sampler = None if scfg.is_greedy else _serial_sampler(scfg)

    def pick(logits_row, pos: int) -> int:
        return _pick_token(logits_row, pos, sampler)

    logits, state = step(params, state, jnp.asarray(prompt[None]))
    if decode_rows > 1:     # cache leaves carry the batch on axis 1
        state = {"caches": jax.tree.map(
            lambda t: jnp.repeat(t, decode_rows, axis=1), state["caches"]),
            "pos": state["pos"]}
    out: List[int] = []
    tok = pick(logits[0, -1], int(prompt.size))
    while True:
        out.append(tok)
        if tok == eos_id or len(out) >= max_new_tokens:
            return out
        logits, state = step(params, state,
                             jnp.full((decode_rows, 1), tok, jnp.int32))
        tok = pick(logits[0, -1], int(prompt.size) + len(out))
