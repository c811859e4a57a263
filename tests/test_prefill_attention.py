"""Fused chunked-prefill attention: the backend ``prefill_attention``
primitive must agree with the masked-einsum oracle on every backend —
bitwise on ``xla`` (it IS the einsum), within f32 tolerance on ``ref`` (the
Pallas cache-continuation kernel in interpret mode) — and must be
*chunk-invariant*: splitting a prompt into ragged chunks (primes, 1-token
tails, window-bucket crossings) may not move one bit of any logit, which is
the property the engine's token-identity contract now rests on."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:      # bare container: skip property tests
    from _hypothesis_stub import given, settings, st

from repro import configs
from repro.kernels import ops
from repro.kernels.backend import available, get_backend, set_backend
from repro.kernels.prefill_attention import prefill_attention_pallas
from repro.models import attention as A
from repro.models import lm
from repro.serving import Engine, Request, SchedulerConfig, serial_decode
from repro.sharding.ctx import default_ctx

B, HQ, HKV, HD = 3, 8, 4, 32
BLOCK = 16


def _cache(key, max_seq, quantized):
    ks = jax.random.split(key, 4)
    if quantized:
        return {
            "k_q": jax.random.randint(ks[0], (B, max_seq, HKV, HD),
                                      -127, 128, jnp.int8),
            "v_q": jax.random.randint(ks[1], (B, max_seq, HKV, HD),
                                      -127, 128, jnp.int8),
            "k_s": jax.random.uniform(ks[2], (B, max_seq, HKV),
                                      jnp.float32, 0.01, 0.1),
            "v_s": jax.random.uniform(ks[3], (B, max_seq, HKV),
                                      jnp.float32, 0.01, 0.1),
        }
    return {"k": jax.random.normal(ks[0], (B, max_seq, HKV, HD),
                                   jnp.bfloat16),
            "v": jax.random.normal(ks[1], (B, max_seq, HKV, HD),
                                   jnp.bfloat16)}


def _kernel_args(cache):
    if "k_q" in cache:
        return (cache["k_q"], cache["v_q"], cache["k_s"], cache["v_s"])
    return (cache["k"], cache["v"], None, None)


# ------------------------------------------------------------ kernel oracle
@pytest.mark.parametrize("sq,bq,bk", [(1, 16, 16), (5, 8, 16), (16, 8, 64),
                                      (17, 16, 16)])
@pytest.mark.parametrize("per_slot", [False, True])
@pytest.mark.parametrize("quantized", [False, True])
def test_prefill_kernel_ref_vs_einsum(quantized, per_slot, sq, bq, bk):
    """Pallas cache-continuation kernel (interpret mode) vs the einsum
    oracle, f32 tolerance: exercises ragged query tiles (sq not a bq
    multiple), the per-slot block skip, the KV-tail padding mask (max_seq
    not a bk multiple), and the fused INT8 dequant epilogue."""
    max_seq = 80                       # not a multiple of 64: padded KV tail
    key = jax.random.PRNGKey(sq * 31 + bq)
    cache = _cache(key, max_seq, quantized)
    q = jax.random.normal(jax.random.fold_in(key, 1), (B, sq, HQ, HD),
                          jnp.bfloat16)
    hi = max_seq - sq
    start = (jnp.asarray([0, hi // 2, hi], jnp.int32) if per_slot
             else jnp.full((B,), hi // 2, jnp.int32))
    oracle = A.cached_attention(q, cache, start)
    out = prefill_attention_pallas(q, *_kernel_args(cache), start,
                                   bq=bq, bk=bk, interpret=True)
    # int8 path: the oracle rounds probabilities AND dequantized V to bf16
    # before its dot while the kernel accumulates f32 — values span ~±12
    # (127 * 0.1 scale), so bf16 rounding alone is ~0.1 absolute
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(oracle, np.float32),
                               rtol=3e-2, atol=1.5e-1 if quantized else 3e-2)


@pytest.mark.parametrize("sq", [1, 7])
@pytest.mark.parametrize("quantized", [False, True])
def test_prefill_attention_xla_bitwise_vs_einsum(quantized, sq):
    """The xla backend's prefill primitive is literally the masked einsum —
    bitwise, windowed or not. Token identity between engine chunked prefill
    and serial whole-prompt prefill hinges on this on the xla backend."""
    max_seq = 64
    key = jax.random.PRNGKey(sq)
    cache = _cache(key, max_seq, quantized)
    q = jax.random.normal(jax.random.fold_in(key, 2), (B, sq, HQ, HD),
                          jnp.bfloat16)
    start = jnp.asarray([1, 9, 24], jnp.int32)
    oracle = A.cached_attention(q, cache, start)
    win = -(-(24 + sq) // BLOCK) * BLOCK
    prev = set_backend("xla")
    try:
        for window in (None, win):
            out = ops.prefill_attention(q, cache, start, window=window)
            np.testing.assert_array_equal(np.asarray(oracle, np.float32),
                                          np.asarray(out, np.float32))
    finally:
        set_backend(prev)


@pytest.mark.parametrize("quantized", [False, True])
def test_prefill_kernel_chunk_invariant_bitwise(quantized):
    """Splitting Sq=13 queries into ragged chunks (5, 7, 1-token tail) and
    widening the visible window must reproduce the whole-chunk kernel output
    BIT-FOR-BIT: causal limits are absolute positions, so chunk boundaries,
    query-tile sizes, and trailing masked KV blocks are all exact no-ops."""
    max_seq, sq = 48, 13
    key = jax.random.PRNGKey(3)
    cache = _cache(key, max_seq, quantized)
    args = _kernel_args(cache)
    q = jax.random.normal(jax.random.fold_in(key, 4), (B, sq, HQ, HD),
                          jnp.bfloat16)
    start0 = jnp.zeros((B,), jnp.int32)
    whole = prefill_attention_pallas(q, *args, start0, bq=8, bk=16,
                                     interpret=True)
    parts = []
    for lo, hi in [(0, 5), (5, 12), (12, 13)]:
        parts.append(prefill_attention_pallas(
            q[:, lo:hi], *args, jnp.full((B,), lo, jnp.int32),
            bq=8, bk=16, interpret=True))
    np.testing.assert_array_equal(
        np.asarray(whole, np.float32),
        np.asarray(jnp.concatenate(parts, axis=1), np.float32))
    # a wider window (more trailing KV blocks) may not move a bit either
    sl = lambda t, n: None if t is None else t[:, :n]
    narrow = prefill_attention_pallas(
        q, args[0][:, :32], args[1][:, :32], sl(args[2], 32), sl(args[3], 32),
        start0, bq=8, bk=16, interpret=True)
    np.testing.assert_array_equal(np.asarray(whole, np.float32),
                                  np.asarray(narrow, np.float32))


def _arena(key, quantized, n_pages, ps):
    """A paged arena as the engine stores it: uint16 words for bf16 KV."""
    from repro.kernels.kv_layout import to_store
    arena = _cache(key, ps, quantized)        # (B, ps, ...) -> re-lead
    arena = {k: jnp.concatenate([v] * (n_pages // B + 1))[:n_pages]
             for k, v in arena.items()}
    if not quantized:
        arena = {k: to_store(v, jnp.uint16) for k, v in arena.items()}
    return arena


@pytest.mark.parametrize("sq", [1, 5, 16])
@pytest.mark.parametrize("quantized", [False, True])
def test_paged_kernel_ref_vs_einsum(quantized, sq):
    """The paged kernel (interpret mode) against the xla paged oracle
    (gather + einsum) through a shuffled page table: the scalar-prefetch
    index maps, the clamped past-the-limit page index, and uint16 words
    bitcast back to bf16 inside the kernel."""
    from repro.kernels.kv_layout import from_store
    from repro.kernels.ref import paged_prefill_attention_ref
    ps, n_blk = 8, 6
    key = jax.random.PRNGKey(sq + 17 * quantized)
    arena = _arena(key, quantized, 1 + B * n_blk, ps)
    pages = jnp.asarray(1 + np.random.RandomState(sq).permutation(
        B * n_blk).reshape(B, n_blk), jnp.int32)
    q = jax.random.normal(jax.random.fold_in(key, 5), (B, sq, HQ, HD),
                          jnp.bfloat16)
    start = jnp.asarray([0, 13, ps * n_blk - sq], jnp.int32)
    args = _kernel_args(arena)
    out = get_backend("ref").prefill_attention_paged(
        q, *args, start, pages)
    oracle = paged_prefill_attention_ref(
        q, *(a if a is None or quantized else from_store(a) for a in args),
        start, pages)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(oracle, np.float32),
                               rtol=3e-2, atol=1.5e-1 if quantized else 3e-2)


@pytest.mark.parametrize("sq", [1, 7])
@pytest.mark.parametrize("quantized", [False, True])
def test_contiguous_kernel_bitwise_equals_paged(quantized, sq):
    """A contiguous cache read in ``bk`` blocks and the same KV in a paged
    arena of ``bk``-position pages give bit-identical results: the online
    softmax folds the same blocks in the same order. On the chip this is
    what keeps a paged engine token-identical to serial (contiguous)
    decode."""
    from repro.kernels.kv_layout import as_pages
    from repro.kernels.prefill_attention import paged_prefill_attention_pallas
    max_seq, bk = 48, 16
    key = jax.random.PRNGKey(sq)
    cache = _cache(key, max_seq, quantized)
    q = jax.random.normal(jax.random.fold_in(key, 6), (B, sq, HQ, HD),
                          jnp.bfloat16)
    start = jnp.asarray([0, 20, max_seq - sq], jnp.int32)
    contiguous = prefill_attention_pallas(q, *_kernel_args(cache), start,
                                          bk=bk, interpret=True)
    k, v, k_s, v_s, ident = as_pages(*_kernel_args(cache), bk)
    # the same pages in reverse physical order, behind a trash page
    n = k.shape[0]
    perm = np.arange(n)[::-1]
    lift = lambda t: None if t is None else jnp.concatenate(
        [jnp.zeros_like(t[:1]), t[perm]])
    table = 1 + jnp.asarray(np.argsort(perm))[ident]
    paged = paged_prefill_attention_pallas(q, lift(k), lift(v), lift(k_s),
                                           lift(v_s), start, table,
                                           interpret=True)
    np.testing.assert_array_equal(np.asarray(contiguous, np.float32),
                                  np.asarray(paged, np.float32))


def test_prefill_attention_registered_on_all_backends():
    """Every registered backend exposes the prefill primitive; every backend
    executable on this platform produces a finite, well-shaped result
    agreeing with `xla` within f32 tolerance."""
    assert set(available()) == {"pallas", "xla", "ref"}
    for name in available():
        assert callable(get_backend(name).prefill_attention)
    key = jax.random.PRNGKey(9)
    cache = _cache(key, 32, False)
    q = jax.random.normal(key, (B, 5, HQ, HD), jnp.bfloat16)
    start = jnp.asarray([0, 5, 27], jnp.int32)
    run = ["xla", "ref"] + (["pallas"] if jax.default_backend() == "tpu"
                            else [])
    outs = {}
    for name in run:
        prev = set_backend(name)
        try:
            outs[name] = np.asarray(
                ops.prefill_attention(q, cache, start), np.float32)
        finally:
            set_backend(prev)
        assert outs[name].shape == (B, 5, HQ, HD)
        assert np.all(np.isfinite(outs[name]))
        np.testing.assert_allclose(outs[name], outs["xla"],
                                   rtol=3e-2, atol=3e-2)


# --------------------------------------------------- engine token identity
@pytest.fixture(scope="module")
def setup():
    cfg = configs.get_smoke_config("qwen3-0.6b")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _identity_sweep(cfg, params, lens, quantized, prefill_chunk,
                    max_new=4, max_seq=64, seed=0):
    """Engine output must equal serial decode token-for-token for every
    prompt length in ``lens`` (run as one staggered batch)."""
    ctx = dataclasses.replace(default_ctx(), quantized_kv=quantized)
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, cfg.vocab_size, n).tolist() for n in lens]
    eng = Engine(params, cfg, ctx=ctx, n_slots=2, max_seq=max_seq,
                 sched=SchedulerConfig(prefill_chunk=prefill_chunk))
    res = eng.run([Request(prompt=p, max_new_tokens=max_new)
                   for p in prompts],
                  arrival_ticks=[2 * i for i in range(len(prompts))])
    for i, p in enumerate(prompts):
        ref = serial_decode(params, cfg, p, max_new, ctx=ctx,
                            max_seq=max_seq)
        assert res[i].tokens == ref, (lens[i], res[i].tokens, ref)


@pytest.mark.parametrize("quantized", [False, True])
def test_engine_chunked_prefill_token_identity_ragged(setup, quantized):
    """Deterministic corner sweep on the session backend (the CI matrix
    runs it under xla AND ref): prime prompt lengths, a 1-token tail chunk
    (16 = 3*5 + 1), and prompts crossing the window_block=16 boundary
    (17, 31) — all bit-identical to serial whole-prompt decode with the
    prefill primitive active."""
    cfg, params = setup
    _identity_sweep(cfg, params, lens=[13, 16, 17, 31], quantized=quantized,
                    prefill_chunk=5)


@given(lens=st.lists(st.integers(1, 40), min_size=1, max_size=3),
       chunk=st.integers(1, 9), quantized=st.booleans())
@settings(max_examples=6, deadline=None)
def test_engine_prefill_token_identity_property(lens, chunk, quantized):
    """Property sweep: ANY ragged prompt lengths × chunk size × KV dtype
    keep engine output == serial decode bit-for-bit."""
    cfg = configs.get_smoke_config("qwen3-0.6b")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    _identity_sweep(cfg, params, lens=lens, quantized=quantized,
                    prefill_chunk=chunk, seed=sum(lens) + chunk)
