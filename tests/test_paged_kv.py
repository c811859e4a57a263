"""Paged KV cache: page-table indirection, prefix reuse, and numerics.

Load-bearing guarantees:
  * the paged engine is TOKEN-IDENTICAL to serial decode at every page
    size — multi-page (16), mid (32), and the ``page_size == max_seq``
    degenerate (contiguous-identity) case — for greedy AND seeded
    sampling, ragged prompts, INT8 KV, and speculative decoding with
    pos-only rollback;
  * a repeated system prompt hits the hash-keyed prefix cache (copy-free
    page mapping; only the tail prefills) and every page is refcounted:
    eviction releases exactly the slot's references, the arena never
    leaks, the trash page stays pinned (hypothesis property over random
    alloc/ref/unref interleavings);
  * ``reset_slot`` in paged mode never touches the shared KV arena
    (recurrent state + pos only) — scrubbing it would corrupt pages other
    slots still reference;
  * ``scripts/check_bench.py`` gates paged throughput parity and the
    shared-prefix memory ceiling by NAME.
"""
import dataclasses
import importlib.util
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:      # bare container: skip property tests
    from _hypothesis_stub import given, settings, st

from repro import configs
from repro.models import lm
from repro.serving import (Engine, Request, SamplingConfig, SchedulerConfig,
                           serial_decode)
from repro.serving import state_pool as sp
from repro.sharding.ctx import default_ctx

ARCH = "qwen3-0.6b"
MAX_SEQ = 64


@pytest.fixture(scope="module")
def setup():
    cfg = configs.get_smoke_config(ARCH)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _prompts(cfg, lens, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size, n).tolist() for n in lens]


def _assert_drained(eng):
    """After a run every slot is evicted: the only live references left
    are the prefix cache's, and the allocator invariants hold."""
    cache_pages = (len({p for v in eng.prefix._entries.values() for p in v})
                   if eng.prefix is not None else 0)
    assert eng.alloc.pages_in_use == cache_pages
    eng.alloc.check()


# ------------------------------------------------------- engine == serial
def test_paged_token_identical_every_page_size(setup):
    """Ragged prompts through multi-page, mid, and degenerate
    (page_size == max_seq) layouts — all three must reproduce the serial
    tokens bit-for-bit; the degenerate case is the contiguous-identity
    anchor."""
    cfg, params = setup
    prompts = _prompts(cfg, [13, 7, 18], seed=2)
    refs = [serial_decode(params, cfg, p, 6, max_seq=MAX_SEQ)
            for p in prompts]
    for ps in (16, 32, MAX_SEQ):
        eng = Engine(params, cfg, n_slots=3, max_seq=MAX_SEQ,
                     sched=SchedulerConfig(prefill_chunk=5), page_size=ps)
        res = eng.run([Request(prompt=p, max_new_tokens=6) for p in prompts])
        for i in range(len(prompts)):
            assert res[i].tokens == refs[i], f"page_size={ps} prompt {i}"
        _assert_drained(eng)


def test_paged_seeded_sampling_matches_serial(setup):
    """Sampling draws with position-derived keys, so paging (which never
    changes logical positions) must not perturb a seeded trace."""
    cfg, params = setup
    scfg = SamplingConfig(temperature=0.8, top_k=8, seed=7)
    prompts = _prompts(cfg, [9, 14], seed=3)
    refs = [serial_decode(params, cfg, p, 5, max_seq=MAX_SEQ, sampling=scfg)
            for p in prompts]
    eng = Engine(params, cfg, n_slots=2, max_seq=MAX_SEQ,
                 sched=SchedulerConfig(prefill_chunk=6), page_size=16,
                 sampling=scfg)
    res = eng.run([Request(prompt=p, max_new_tokens=5) for p in prompts])
    for i in range(len(prompts)):
        assert res[i].tokens == refs[i]
    _assert_drained(eng)


def test_paged_int8_kv_token_identical(setup):
    """INT8 KV quantizes per token at write time, so the paged gather must
    dequantize the same bits the contiguous path would."""
    cfg, params = setup
    from repro.compress import compress
    art = compress(params, cfg, log=lambda s: None)
    ctx_q = dataclasses.replace(default_ctx(), quantized_kv=True)
    prompts = _prompts(cfg, [11, 17], seed=4)
    refs = [serial_decode(art.params, cfg, p, 5, ctx=ctx_q, max_seq=MAX_SEQ)
            for p in prompts]
    eng = Engine(art.params, cfg, ctx=ctx_q, n_slots=2, max_seq=MAX_SEQ,
                 sched=SchedulerConfig(prefill_chunk=8), page_size=16)
    res = eng.run([Request(prompt=p, max_new_tokens=5) for p in prompts])
    for i in range(len(prompts)):
        assert res[i].tokens == refs[i]
    _assert_drained(eng)


def test_paged_speculative_rollback_token_identical(setup):
    """Speculative decode over paged pools: draft and verify arenas share
    ONE table, rejection rolls back by pos only (pages stay mapped), and
    greedy output still equals serial bf16."""
    cfg, params = setup
    from repro.compress import compress
    art = compress(params, cfg, log=lambda s: None)
    ctx_q = dataclasses.replace(default_ctx(), quantized_kv=True)
    prompts = _prompts(cfg, [13, 7], seed=2)
    refs = [serial_decode(params, cfg, p, 6, max_seq=MAX_SEQ)
            for p in prompts]
    for ps in (16, MAX_SEQ):
        eng = Engine(params, cfg, n_slots=2, max_seq=MAX_SEQ,
                     sched=SchedulerConfig(prefill_chunk=8),
                     draft_params=art.params, spec_k=3, draft_ctx=ctx_q,
                     page_size=ps)
        res = eng.run([Request(prompt=p, max_new_tokens=6) for p in prompts])
        for i in range(len(prompts)):
            assert res[i].tokens == refs[i], f"page_size={ps} prompt {i}"
        assert eng.stats["drafted_tokens"] > 0
        _assert_drained(eng)


# ------------------------------------------------- layer-indexed KV write
@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("quantized", [False, True], ids=["uint16", "int8"])
def test_layer_indexed_paged_write_touches_only_its_positions(quantized,
                                                              layer):
    """``update_kv_cache`` on a stacked arena merged into one
    (``merge_layers``), through a table offset to layer ``layer``, writes
    that layer at the (page, offset) its table names, scales at lane
    ``h*page_size + offset`` of the page's row, and nothing else: every
    other byte of the stacked K, V and scale leaves stays equal. Row 1's
    table is the trash page throughout (an inactive row): its write lands
    on that layer's page 0."""
    from repro.kernels.kv_layout import (arena_pages, merge_layers,
                                         split_layers, to_store)
    from repro.models import attention as A
    groups, ps, hkv, hd, sn = 3, 4, 2, 8, 3
    key = jax.random.PRNGKey(layer + 10 * quantized)
    one = A.init_kv_cache(arena_pages(12), ps, hkv, hd, quantized,
                          paged=True)
    stacked = {}
    for i, (name, leaf) in enumerate(sorted(one.items())):
        shape, k = (groups,) + leaf.shape, jax.random.fold_in(key, i)
        stacked[name] = (
            jax.random.uniform(k, shape, jnp.float32, 0.01, 0.1)
            if leaf.dtype == jnp.float32
            else jax.random.randint(k, shape, -128, 128).astype(leaf.dtype)
            if leaf.dtype == jnp.int8
            else jax.random.bits(k, shape, jnp.uint16))
    table = jnp.asarray([[3, 7, 5], [0, 0, 0], [11, 2, 9]], jnp.int32)
    pos = jnp.asarray([2, 5, 7], jnp.int32)
    k_new, v_new = (jax.random.normal(jax.random.fold_in(key, 9 + i),
                                      (3, sn, hkv, hd), jnp.bfloat16)
                    for i in range(2))

    @jax.jit
    def write(arena, g):
        merged, n_pages = merge_layers(arena)
        return split_layers(A.update_kv_cache(merged, k_new, v_new, pos,
                                              pages=table + g * n_pages),
                            groups)

    out = write(stacked, jnp.int32(layer))
    if quantized:
        # quantized under jit, as the write quantizes
        quant = jax.jit(A._quant_kv)
        (kq, ks), (vq, vs) = quant(k_new), quant(v_new)
        new = {"k_q": kq, "v_q": vq, "k_s": ks, "v_s": vs}
    else:
        new = {"k": to_store(k_new, jnp.uint16),
               "v": to_store(v_new, jnp.uint16)}
    want = {name: np.array(leaf) for name, leaf in stacked.items()}
    for b in range(3):
        for t in range(sn):
            p = int(pos[b]) + t
            page, off = int(table[b, p // ps]), p % ps
            for name, val in new.items():
                val = np.asarray(val[b, t])
                if name in ("k_s", "v_s"):
                    want[name][layer, page, off::ps] = val
                else:
                    want[name][layer, page, off] = val
    assert sorted(out) == sorted(want)
    for name in want:
        np.testing.assert_array_equal(np.asarray(out[name]), want[name],
                                      err_msg=name)


# --------------------------------------------------------- prefix sharing
def test_prefix_reuse_skips_prefill_and_stays_identical(setup):
    """Requests repeating a page-aligned system prompt: later admissions
    map the cached pages copy-free (>= 1 hit each once the cache is warm),
    prefill only covers the tails, and the tokens still match serial."""
    cfg, params = setup
    rng = np.random.RandomState(5)
    head = rng.randint(0, cfg.vocab_size, 32).tolist()
    reqs = [Request(prompt=head + rng.randint(0, cfg.vocab_size, 5).tolist(),
                    max_new_tokens=4) for _ in range(4)]
    eng = Engine(params, cfg, n_slots=2, max_seq=MAX_SEQ,
                 sched=SchedulerConfig(prefill_chunk=8), page_size=16)
    res = eng.run(reqs)
    for i, r in enumerate(reqs):
        ref = serial_decode(params, cfg, r.prompt, 4, max_seq=MAX_SEQ)
        assert res[i].tokens == ref, f"request {i}"
    st_ = eng.stats
    # 2 slots admit the first two requests before either inserts, so the
    # floor is hits on every LATER admission, not all four
    assert st_["prefix_hits"] >= 2
    assert st_["prefix_hit_tokens"] >= 2 * 32
    assert st_["bytes_saved"] > 0
    assert st_["prefill_tokens"] < sum(len(r.prompt) for r in reqs)
    assert st_["pages_peak"] <= eng.total_pages - 1
    _assert_drained(eng)


def test_prefix_cache_disabled_still_identical(setup):
    cfg, params = setup
    rng = np.random.RandomState(6)
    head = rng.randint(0, cfg.vocab_size, 16).tolist()
    reqs = [Request(prompt=head + rng.randint(0, cfg.vocab_size, 3).tolist(),
                    max_new_tokens=3) for _ in range(2)]
    eng = Engine(params, cfg, n_slots=2, max_seq=MAX_SEQ,
                 sched=SchedulerConfig(prefill_chunk=8), page_size=16,
                 prefix_cache=False)
    res = eng.run(reqs)
    for i, r in enumerate(reqs):
        ref = serial_decode(params, cfg, r.prompt, 3, max_seq=MAX_SEQ)
        assert res[i].tokens == ref
    assert eng.stats["prefix_hits"] == 0
    assert eng.alloc.pages_in_use == 0      # nothing retained
    eng.alloc.check()


# ------------------------------------------------- allocator / cache units
def test_page_allocator_exhaustion_and_reuse():
    alloc = sp.PageAllocator(5)              # trash + 4 usable
    a = alloc.alloc(4)
    assert sorted(a) == [1, 2, 3, 4] and alloc.free_pages == 0
    with pytest.raises(MemoryError):
        alloc.alloc(1)
    alloc.unref([a[0]])
    assert alloc.alloc(1) == [a[0]]          # freed page comes back
    alloc.check()


def test_prefix_cache_longest_aligned_proper_prefix():
    alloc = sp.PageAllocator(9)
    cache = sp.PrefixCache(alloc, page_size=4)
    prompt = np.arange(12, dtype=np.int32)
    pages = alloc.alloc(3)
    assert cache.insert(prompt, pages, 12) == 12
    # exact repeat: hit caps at 8 tokens (align_down(12-1, 4)) so one
    # prompt token always prefills
    hit, got = cache.lookup(prompt)
    assert hit == 8 and got == pages[:2]
    alloc.unref(got)
    # longer prompt with the same head: full 12-token entry hits
    hit, got = cache.lookup(np.arange(14, dtype=np.int32))
    assert hit == 12 and got == pages
    alloc.unref(got)
    # diverging head: miss
    assert cache.lookup(np.full(12, 99, np.int32)) == (0, [])
    cache.clear()
    alloc.unref(pages)
    assert alloc.pages_in_use == 0
    alloc.check()


def test_prefix_cache_lru_eviction_unrefs():
    alloc = sp.PageAllocator(9)
    cache = sp.PrefixCache(alloc, page_size=4)
    p1 = alloc.alloc(1)
    p2 = alloc.alloc(1)
    cache.insert(np.arange(4, dtype=np.int32), p1, 4)
    cache.insert(np.arange(10, 14, dtype=np.int32), p2, 4)
    alloc.unref(p1 + p2)                     # cache holds the only refs
    assert alloc.pages_in_use == 2
    assert cache.evict_lru()                 # drops the p1 entry (oldest)
    assert alloc.refs[p1[0]] == 0 and alloc.refs[p2[0]] == 1
    assert cache.evict_lru() and not cache.evict_lru()
    assert alloc.pages_in_use == 0
    alloc.check()


@settings(deadline=None, max_examples=60)
@given(st.lists(st.tuples(st.integers(0, 2), st.integers(1, 4)),
                max_size=40))
def test_page_table_roundtrip_property(ops):
    """Random alloc/ref/unref interleavings (the host-side shape of
    admit -> share -> rollback -> evict): a shadow refcount model must
    agree with the allocator at every step, no page is ever handed out
    while live, and draining all references returns the arena to empty —
    leak-free and double-free-safe by construction."""
    alloc = sp.PageAllocator(9)
    live = {}                                # page -> refs we hold
    for op, n in ops:
        if op == 0:                          # admit: alloc n pages
            try:
                pages = alloc.alloc(n)
            except MemoryError:
                assert alloc.free_pages < n
                continue
            assert not set(pages) & set(live), "live page re-allocated"
            for p in pages:
                live[p] = 1
        elif op == 1 and live:               # share: ref n existing pages
            pages = sorted(live)[:n]
            alloc.ref(pages)
            for p in pages:
                live[p] += 1
        elif op == 2 and live:               # evict/rollback: drop refs
            pages = sorted(live)[:n]
            alloc.unref(pages)
            for p in pages:
                live[p] -= 1
                if live[p] == 0:
                    del live[p]
        assert alloc.pages_in_use == len(live)
        for p, r in live.items():
            assert alloc.refs[p] == r
        alloc.check()
    for p, r in list(live.items()):          # drain
        alloc.unref([p] * r)
    assert alloc.pages_in_use == 0 and alloc.free_pages == 8
    alloc.check()


@settings(deadline=None, max_examples=60)
@given(st.lists(st.tuples(st.integers(0, 3), st.integers(1, 3)),
                max_size=30))
def test_prefix_cache_refcount_property(ops):
    """Random insert/lookup/release/evict interleavings of slots against
    the prefix cache — the host-side shape of a cancel/evict storm over
    shared prefixes. Invariants: the allocator's refcount bookkeeping
    stays coherent at every step, a hit always returns a prefix of the
    inserting slot's pages, and releasing every slot ref plus clearing
    the cache returns the arena to empty (cache refs and slot refs never
    get conflated)."""
    ps = 4
    alloc = sp.PageAllocator(12)
    cache = sp.PrefixCache(alloc, page_size=ps)
    inserted = []                            # (prompt_core, pages)
    held = []                                # page lists we hold refs on
    base = 0
    for op, n in ops:
        if op == 0:                          # prefill a fresh prompt + insert
            while True:                      # engine's evict-then-retry loop
                try:
                    pages = alloc.alloc(n)
                    break
                except MemoryError:
                    if not cache.evict_lru():
                        pages = None
                        break
            if pages is None:
                continue
            prompt = np.arange(base, base + n * ps, dtype=np.int32)
            base += n * ps                   # unique tokens => unique keys
            shared = cache.insert(prompt, pages, n * ps)
            assert shared == n * ps
            inserted.append((prompt, pages))
            held.append(pages)               # the slot keeps its own refs
        elif op == 1 and inserted:           # a later request shares a head
            prompt, pages = inserted[n % len(inserted)]
            probe = np.concatenate(
                [prompt, np.full(2, -1, dtype=np.int32)])
            hit_n, hit_pages = cache.lookup(probe)
            if hit_n:                        # LRU may have dropped it
                assert hit_n % ps == 0
                assert hit_pages == list(pages[:hit_n // ps])
                held.append(hit_pages)       # lookup ref'd them for us
        elif op == 2 and held:               # slot finishes / is cancelled
            alloc.unref(held.pop(n % len(held)))
        elif op == 3:                        # arena pressure
            cache.evict_lru()
        alloc.check()
    for pages in held:                       # every slot drains
        alloc.unref(pages)
    cache.clear()
    assert alloc.pages_in_use == 0 and alloc.free_pages == 11
    alloc.check()


# ------------------------------------------------------------- reset_slot
def test_reset_slot_paged_leaves_kv_arena_alone(setup):
    """Admission reset must not write the shared arena: KV leaves come
    back as the SAME buffers (pos=0 makes stale KV unreachable), only
    recurrent state and pos reset."""
    cfg, params = setup
    ctx = default_ctx()
    pool = sp.init_paged_pool(cfg, 2, 32, ctx, params=None,
                              page_size=16, total_pages=5)
    template = sp.init_slot_template(cfg, 32, ctx, params=None)
    out = jax.jit(
        lambda pl: sp.reset_slot(pl, jnp.int32(1), template,
                                 pos0=jnp.int32(3), paged=True),
        donate_argnums=())(pool)
    kv_in = [lf for e in _kv_leaves(pool) for lf in e]
    kv_out = [lf for e in _kv_leaves(out) for lf in e]
    for a, b in zip(kv_in, kv_out):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(out["pos"][1]) == 3


def _kv_leaves(pool):
    return [jax.tree.leaves(e) for e in pool["caches"] if sp.is_kv_entry(e)]


# -------------------------------------------------------------- check_bench
def _load_check_bench():
    path = (pathlib.Path(__file__).resolve().parents[1] / "scripts"
            / "check_bench.py")
    spec = importlib.util.spec_from_file_location("check_bench", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _bench_doc(tmp_path, variants, expected):
    doc = {"schema": "repro-bench/v1",
           "rows": [{"name": "serving/x", "us_per_call": 1.0,
                     "derived": "ok"}],
           "errors": [],
           "serving": {"schema": "repro-bench-serving/v1",
                       "expected_variants": expected,
                       "variants": variants}}
    p = tmp_path / "BENCH_pr.json"
    p.write_text(json.dumps(doc))
    return p


def _variant(**kw):
    v = {"n_requests": 3, "tokens_per_s": 100.0, "latency_p50_ms": 1.0,
         "latency_p95_ms": 2.0, "ttft_p50_ms": 1.0, "ttft_p95_ms": 2.0,
         "param_bytes": 10, "out_tokens": 30}
    v.update(kw)
    return v


def _shared_variant(**kw):
    v = _variant(prefix_hits=6, prefill_tokens=66, prompt_tokens=450,
                 kv_bytes_peak=80, contiguous_kv_bytes=200)
    v.update(kw)
    return v


def test_check_bench_names_missing_paged_variant(tmp_path, capsys):
    cb = _load_check_bench()
    path = _bench_doc(tmp_path, {"paged": _variant()}, ["paged"])
    with pytest.raises(SystemExit):
        cb.main([str(path)])
    assert "needs variant 'paged_baseline'" in capsys.readouterr().out


def test_check_bench_gates_paged_throughput_floor(tmp_path, capsys):
    cb = _load_check_bench()
    path = _bench_doc(tmp_path, {
        "paged": _variant(tokens_per_s=80.0),
        "paged_baseline": _variant(tokens_per_s=100.0),
        "paged_shared": _shared_variant()}, [])
    with pytest.raises(SystemExit):
        cb.main([str(path)])
    assert "no longer free" in capsys.readouterr().out


def test_check_bench_gates_shared_bytes_ceiling(tmp_path, capsys):
    cb = _load_check_bench()
    path = _bench_doc(tmp_path, {
        "paged": _variant(),
        "paged_baseline": _variant(),
        "paged_shared": _shared_variant(kv_bytes_peak=150)}, [])
    with pytest.raises(SystemExit):
        cb.main([str(path)])
    assert "contiguous footprint" in capsys.readouterr().out


def test_check_bench_gates_prefix_must_hit(tmp_path, capsys):
    cb = _load_check_bench()
    path = _bench_doc(tmp_path, {
        "paged": _variant(),
        "paged_baseline": _variant(),
        "paged_shared": _shared_variant(prefix_hits=0)}, [])
    with pytest.raises(SystemExit):
        cb.main([str(path)])
    assert "zero prefix hits" in capsys.readouterr().out


def test_check_bench_accepts_healthy_paged(tmp_path):
    cb = _load_check_bench()
    path = _bench_doc(tmp_path, {
        "paged": _variant(tokens_per_s=99.0),
        "paged_baseline": _variant(tokens_per_s=100.0),
        "paged_shared": _shared_variant()},
        ["paged", "paged_baseline", "paged_shared"])
    assert cb.main([str(path)]) == 0
