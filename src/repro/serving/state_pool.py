"""Per-slot decode-state pool for the continuous-batching engine.

The pool is the ``lm.init_decode_state`` pytree with two twists:

  * ``pos`` is a (n_slots,) vector — every slot advances independently;
  * every cache leaf keeps the seed layout (groups, n_slots, ...), i.e. the
    slot axis is **axis 1** of every leaf under ``state["caches"]`` (axis 0 is
    the lax.scan group stack). ``SLOT_AXIS`` pins that invariant.

Built on ``init_decode_state(..., params=...)`` so HQP-compacted artifacts —
whose pruned KV heads / Mamba channels physically shrank — size their own
pool; the engine never consults the config for cache widths.

Slot ops are pure functions (jitted by the engine), each under a
``jax.named_scope`` that names its device operations in a profile
(``kv_gather``, ``kv_scatter``, ``kv_reset``, ``kv_select``):

  gather_slot(pool, slot)          -> single-slot state (batch=1, scalar pos)
  scatter_slot(pool, slot, state)  -> pool with that slot replaced
  reset_slot(pool, slot, template, pos0)
                                   -> pool with the slot reset (admission)

``gather``/``scatter`` use dynamic_slice with a *traced* slot index, so one
compiled executable serves every slot.

PAGED mode (DESIGN.md §12): ``init_paged_pool`` swaps the per-slot KV
layout for a global page arena — KV leaves become (groups, n_pages,
page_size, Hkv, hd) (INT8 scales (groups, n_pages, Hkv*page_size)) with
NO slot axis, and each slot owns a page-table row
(host-side, ``PageAllocator``/``PrefixCache``; the engine uploads the table
per dispatch as ``state["pages"]``). Every slot op takes ``paged=True`` and
splits cache entries by kind: position-indexed KV entries live in the
shared arena (carried through whole — per-slot slicing is meaningless
there), recurrent Mamba/xLSTM entries keep the slotted layout and the
existing dynamic-slice machinery. The contiguous layout stays the
degenerate ``page_size == max_seq`` case: one page per slot, table row i =
[i+1], bit-identical outputs (the token-identity hinge for tests).
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import lm
from repro.models.attention import is_kv_entry  # noqa: F401 (re-export)

SLOT_AXIS = 1   # slot axis of every leaf under pool["caches"]
TRASH_PAGE = 0  # reserved physical page: inactive dispatch rows' tables are
                # redirected here so their garbage writes never touch a live
                # page (the shared arena cannot be select-masked per slot)


def init_pool(cfg, n_slots: int, max_seq: int, ctx=None,
              params: Optional[dict] = None) -> Dict[str, Any]:
    """Pool for ``n_slots`` concurrent requests (per-slot ``pos``)."""
    return lm.init_decode_state(cfg, n_slots, max_seq, ctx, params=params,
                                per_slot_pos=True)


def init_paged_pool(cfg, n_slots: int, max_seq: int, ctx=None,
                    params: Optional[dict] = None, *, page_size: int,
                    total_pages: int) -> Dict[str, Any]:
    """Pool whose KV caches are a shared (total_pages, page_size) arena.

    Physical page ``TRASH_PAGE`` (0) is reserved — ``PageAllocator`` never
    hands it out — so ``total_pages`` should budget one page of slack over
    the live working set."""
    return lm.init_decode_state(cfg, n_slots, max_seq, ctx, params=params,
                                per_slot_pos=True,
                                kv_pages=(total_pages, page_size))


def init_slot_template(cfg, max_seq: int, ctx=None,
                       params: Optional[dict] = None) -> Dict[str, Any]:
    """A fresh single-slot state (batch=1, scalar pos) — the recurrent
    entries are written into the pool on admission (``reset_slot``; the KV
    entries are never read from the template — stale KV is causally
    masked), and the state shape prefill/gather round-trips."""
    return lm.init_decode_state(cfg, 1, max_seq, ctx, params=params)


def _map_entries(pool_caches, fn_kv, fn_rec, *other_caches):
    """Apply ``fn_kv`` to KV entries and ``fn_rec`` to recurrent entries,
    zipping any extra cache tuples (template/update states) leaf-wise."""
    out = []
    for i, entry in enumerate(pool_caches):
        fn = fn_kv if is_kv_entry(entry) else fn_rec
        out.append(jax.tree.map(fn, entry, *(o[i] for o in other_caches)))
    return tuple(out)


def gather_slot(pool: Dict[str, Any], slot: jax.Array,
                paged: bool = False) -> Dict[str, Any]:
    """Extract slot ``slot`` as a batch=1 ``decode_step`` state. In paged
    mode KV entries are the shared arena and pass through whole — the
    caller attaches the slot's page-table row as ``state["pages"]``."""
    sl = lambda leaf: jax.lax.dynamic_slice_in_dim(leaf, slot, 1, SLOT_AXIS)
    with jax.named_scope("kv_gather"):
        caches = (_map_entries(pool["caches"], lambda leaf: leaf, sl)
                  if paged else jax.tree.map(sl, pool["caches"]))
        pos = jax.lax.dynamic_slice(pool["pos"], (slot,), (1,))[0]
    return {"caches": caches, "pos": pos}


def scatter_slot(pool: Dict[str, Any], slot: jax.Array,
                 state: Dict[str, Any], paged: bool = False
                 ) -> Dict[str, Any]:
    """Write a batch=1 state back into slot ``slot``. In paged mode the KV
    entries of ``state`` ARE the updated arena (the paged write already
    landed through the page table) and replace the pool's wholesale."""
    upd = lambda leaf, u: jax.lax.dynamic_update_slice_in_dim(
        leaf, u.astype(leaf.dtype), slot, SLOT_AXIS)
    with jax.named_scope("kv_scatter"):
        caches = (_map_entries(pool["caches"], lambda leaf, u: u, upd,
                               state["caches"])
                  if paged else jax.tree.map(upd, pool["caches"],
                                             state["caches"]))
        pos = jax.lax.dynamic_update_slice(
            pool["pos"], jnp.reshape(state["pos"], (1,)).astype(jnp.int32),
            (slot,))
    return {"caches": caches, "pos": pos}


def reset_slot(pool: Dict[str, Any], slot: jax.Array,
               template: Dict[str, Any], pos0: jax.Array = 0,
               paged: bool = False) -> Dict[str, Any]:
    """Reset a slot for a newly admitted request: recurrent Mamba/xLSTM
    entries are scattered from the template (they genuinely need zeroing —
    recurrent state advances irreversibly) and ``pos`` drops to ``pos0``.
    KV entries are NOT touched in either layout: stale KV from the previous
    occupant sits at positions >= pos0 where every later attend masks it by
    the absolute causal limit, and prefill overwrites it before it could
    ever become visible — skipping the template scatter saves a whole-cache
    write per admission (at high admit churn, the dominant reset cost).

    ``pos0`` is 0 for a fresh prompt; a prefix-cache hit admits at the
    shared prefix length (the slot's table already maps the cached
    pages)."""
    upd = lambda leaf, u: jax.lax.dynamic_update_slice_in_dim(
        leaf, u.astype(leaf.dtype), slot, SLOT_AXIS)
    with jax.named_scope("kv_reset"):
        caches = _map_entries(pool["caches"], lambda leaf, u: leaf, upd,
                              template["caches"])
        pos = jax.lax.dynamic_update_slice(
            pool["pos"],
            jnp.reshape(jnp.asarray(pos0, jnp.int32), (1,)), (slot,))
    return {"caches": caches, "pos": pos}


def rollback_slots(pool: Dict[str, Any], pos: jax.Array,
                   active: jax.Array) -> Dict[str, Any]:
    """Roll each active slot's decode position back to ``pos`` (B,) after a
    speculative verify pass over-wrote K+1 candidate positions.

    Only ``pos`` moves — the cache buffers keep the rejected candidates'
    stale KV, which is safe for the same reason slot reuse is: every later
    attend masks by the absolute causal limit (``kv_pos <= start + i``), so
    positions at or past the rolled-back ``pos`` are invisible until a later
    write replaces them, and writes always precede the attend that could
    first see them. This only holds for position-indexed (KV) caches;
    recurrent Mamba/xLSTM states advance irreversibly, which is why the
    speculative decoder refuses non-attention patterns."""
    return {"caches": pool["caches"],
            "pos": jnp.where(active, pos.astype(jnp.int32), pool["pos"])}


def select_slots(new: Dict[str, Any], old: Dict[str, Any],
                 active: jax.Array, paged: bool = False) -> Dict[str, Any]:
    """Per-slot select: keep ``new`` where ``active`` (B,) bool, else ``old``.

    Applied after every batched decode step — including each iteration of
    the engine's multi-step on-device ``lax.scan``, where ``active`` is the
    live mask (slots that hit EOS or their token budget mid-scan freeze
    here) — so inactive slots are bit-untouched: without this, the dummy
    tokens fed to them would pollute their recurrent states and creep
    ``pos``.

    Paged KV entries cannot be select-masked (the arena has no slot axis):
    they pass through from ``new`` wholesale, and inactive slots are instead
    protected at dispatch time — the engine redirects their page-table rows
    to ``TRASH_PAGE``, so their garbage writes land on the reserved page and
    their live pages are never addressed at all."""
    def sel(n, o):
        mask = active.reshape((1, -1) + (1,) * (n.ndim - 2))
        return jnp.where(mask, n, o)

    with jax.named_scope("kv_select"):
        caches = (_map_entries(new["caches"], lambda n, o: n, sel,
                               old["caches"])
                  if paged else jax.tree.map(sel, new["caches"],
                                             old["caches"]))
        pos = jnp.where(active, new["pos"], old["pos"])
    return {"caches": caches, "pos": pos}


# --------------------------------------------------------- host-side paging
class PageAllocator:
    """Host-side free-list allocator with refcounts over the KV page arena.

    Physical page 0 is ``TRASH_PAGE`` and never allocated. Sharing is
    refcount-based: a prefix-cache hit bumps the refcount of each shared
    page (``ref``); eviction and copy-on-write drop it (``unref``), and the
    page returns to the free list when the count hits zero. Pure Python —
    allocation happens on the host between dispatches, never inside jit."""

    def __init__(self, total_pages: int):
        if total_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is the trash page)")
        self.total_pages = total_pages
        self.refs = np.zeros(total_pages, dtype=np.int32)
        self.refs[TRASH_PAGE] = 1   # permanently pinned
        self._free: List[int] = list(range(total_pages - 1, 0, -1))

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def pages_in_use(self) -> int:
        return self.total_pages - 1 - len(self._free)

    def alloc(self, n: int = 1) -> List[int]:
        """Allocate ``n`` fresh pages (refcount 1). Raises MemoryError when
        the arena is exhausted — the engine catches this and evicts from the
        prefix cache before retrying."""
        if n > len(self._free):
            raise MemoryError(
                f"KV arena exhausted: want {n} pages, {len(self._free)} free")
        out = [self._free.pop() for _ in range(n)]
        for p in out:
            self.refs[p] = 1
        return out

    def ref(self, pages) -> None:
        for p in pages:
            assert self.refs[p] > 0, f"ref of dead page {p}"
            self.refs[p] += 1

    def unref(self, pages) -> None:
        for p in pages:
            assert p != TRASH_PAGE and self.refs[p] > 0, f"bad unref {p}"
            self.refs[p] -= 1
            if self.refs[p] == 0:
                self._free.append(int(p))

    def check(self) -> None:
        """Invariant check (tests): every page is either free (ref 0) or
        referenced, never both; the trash page stays pinned."""
        free = set(self._free)
        assert len(free) == len(self._free), "duplicate pages on free list"
        assert TRASH_PAGE not in free and self.refs[TRASH_PAGE] == 1
        for p in range(self.total_pages):
            assert (self.refs[p] == 0) == (p in free), \
                f"page {p}: refs={self.refs[p]}, free={p in free}"


class PrefixCache:
    """Hash-keyed shared-prefix page cache (LRU).

    Keys are the raw bytes of page-aligned prompt heads: an entry for
    ``k`` pages maps ``prompt[:k*page_size].tobytes()`` to the k physical
    page ids holding that prefix's KV. Lookup walks candidate lengths
    longest-first and returns the first hit; the hit caps at
    ``align_down(prompt_len - 1, page_size)`` so at least one prompt token
    always goes through prefill (the engine needs its logits for the first
    sampled token). Hit pages are ref'd for the requesting slot — mapping
    is copy-free; the slot only prefills the tail. Prefix KV bits are
    chunking-independent (rope/projection/quantization are all per-token),
    so reuse is bit-exact regardless of how the original prompt was
    chunked."""

    def __init__(self, alloc: PageAllocator, page_size: int):
        self.alloc = alloc
        self.page_size = page_size
        self._entries: "OrderedDict[bytes, List[int]]" = OrderedDict()

    def __len__(self) -> int:
        return len(self._entries)

    def lookup(self, prompt: np.ndarray) -> Tuple[int, List[int]]:
        """Longest page-aligned proper-prefix hit: (n_tokens, page ids),
        with every returned page ref'd for the caller. (0, []) on miss."""
        ps = self.page_size
        for k in range((len(prompt) - 1) // ps, 0, -1):
            key = np.ascontiguousarray(prompt[:k * ps]).tobytes()
            pages = self._entries.get(key)
            if pages is not None:
                self._entries.move_to_end(key)
                self.alloc.ref(pages)
                return k * ps, list(pages)
        return 0, []

    def insert(self, prompt: np.ndarray, pages: List[int],
               n_tokens: int) -> int:
        """Register every page-aligned prefix of a freshly prefilled prompt
        (``pages`` = the slot's table row, ``n_tokens`` = prompt length).
        Returns the longest number of tokens now cached — the slot's pages
        up to that point are shared and must be treated copy-on-write."""
        ps = self.page_size
        shared = 0
        for k in range(1, n_tokens // ps + 1):
            key = np.ascontiguousarray(prompt[:k * ps]).tobytes()
            if key in self._entries:
                self._entries.move_to_end(key)
            else:
                entry = list(pages[:k])
                self.alloc.ref(entry)
                self._entries[key] = entry
            shared = k * ps
        return shared

    def evict_lru(self) -> bool:
        """Drop the least-recently-used entry, unref'ing its pages. Returns
        False when the cache is empty (arena pressure is then real — the
        engine's alloc retry will raise)."""
        if not self._entries:
            return False
        _, pages = self._entries.popitem(last=False)
        self.alloc.unref(pages)
        return True

    def clear(self) -> None:
        while self.evict_lru():
            pass
