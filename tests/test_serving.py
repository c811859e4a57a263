"""Continuous-batching engine: slot lifecycle, scheduling, and numerics.

The load-bearing guarantees:
  * admission with a full batch queues; eviction on EOS frees the slot;
  * interleaved chunked prefill + batched decode is TOKEN-IDENTICAL to the
    serial single-request path (the acceptance bar for `serve --engine`);
  * an HQP ``QuantizedLinear`` artifact serves through the engine with the
    same tokens as raw ``decode_step`` on that artifact.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro import configs
from repro.models import lm
from repro.serving import (Engine, Request, SchedulerConfig, serial_decode)
from repro.serving import state_pool as sp
from repro.sharding.ctx import default_ctx

ARCH = "qwen3-0.6b"


@pytest.fixture(scope="module")
def setup():
    cfg = configs.get_smoke_config(ARCH)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _prompts(cfg, lens, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size, n).tolist() for n in lens]


# ------------------------------------------------------------ slot lifecycle
def test_admission_with_full_batch_queues(setup):
    cfg, params = setup
    eng = Engine(params, cfg, n_slots=2, max_seq=64,
                 sched=SchedulerConfig(prefill_chunk=8))
    reqs = [Request(prompt=p, max_new_tokens=4)
            for p in _prompts(cfg, [6, 6, 6, 6])]
    uids = [eng.submit(r) for r in reqs]
    assert eng.n_active == 0 and len(eng.waiting) == 4
    peak = 0
    results = {}
    while eng.has_work:
        for res in eng.step():
            results[res.uid] = res
        peak = max(peak, eng.n_active)
        assert eng.n_active <= 2          # batch never exceeds slot count
    assert peak == 2                       # ...but does fill up
    assert sorted(results) == sorted(uids)
    assert all(len(r.tokens) == 4 for r in results.values())


def test_eviction_on_eos_frees_slot_for_waiting(setup):
    cfg, params = setup
    prompts = _prompts(cfg, [8, 8, 8], seed=1)
    # find what the model actually emits first for prompt 0, use it as EOS
    first_tok = serial_decode(params, cfg, prompts[0], 1, max_seq=64)[0]
    eng = Engine(params, cfg, n_slots=1, max_seq=64)
    eos_req = Request(prompt=prompts[0], max_new_tokens=10, eos_id=first_tok)
    long_req = Request(prompt=prompts[1], max_new_tokens=3)
    u0, u1 = eng.submit(eos_req), eng.submit(long_req)
    results = {}
    admit_order = []
    while eng.has_work:
        busy_before = {s.idx for s in eng.slots if s.stage != "free"}
        for res in eng.step():
            results[res.uid] = res
        for s in eng.slots:
            if s.stage != "free" and s.idx not in busy_before and s.result:
                admit_order.append(s.result.uid)
    assert results[u0].finish_reason == "eos"
    assert results[u0].tokens == [first_tok]      # stopped at EOS, slot freed
    assert results[u1].finish_reason == "length"
    assert len(results[u1].tokens) == 3           # waiting request completed


# ------------------------------------------------------------------ numerics
def test_interleaved_prefill_decode_token_identical(setup):
    """3 overlapping requests, staggered arrivals, chunked prefill — outputs
    must equal serial whole-prompt prefill + per-token decode exactly."""
    cfg, params = setup
    prompts = _prompts(cfg, [13, 7, 18], seed=2)
    reqs = [Request(prompt=p, max_new_tokens=6) for p in prompts]
    eng = Engine(params, cfg, n_slots=3, max_seq=64,
                 sched=SchedulerConfig(prefill_chunk=5))
    uids = [eng.submit(r) for r in reqs[:1]]
    results = {}
    # stagger: submit the rest mid-flight so prefill interleaves decode
    for tick in range(1000):
        if not eng.has_work and len(results) == 3:
            break
        if tick == 2:
            uids.append(eng.submit(reqs[1]))
        if tick == 6:
            uids.append(eng.submit(reqs[2]))
        for res in eng.step():
            results[res.uid] = res
    assert eng.stats["decode_ticks"] > 0 and eng.stats["prefill_ticks"] >= 3
    # the reference decodes one row, or the engine's slot width (the form
    # serve --verify uses; equal wherever XLA is batch-invariant)
    for uid, prompt in zip(uids, prompts):
        for rows in (1, eng.n_slots):
            ref = serial_decode(params, cfg, prompt, 6, max_seq=64,
                                decode_rows=rows)
            assert results[uid].tokens == ref, (uid, rows, ref)


def test_engine_matches_decode_step_on_artifact(setup):
    """Engine on a QuantizedLinear artifact == raw decode_step greedy loop
    on the same artifact (INT8 weights + INT8 KV cache)."""
    cfg, params = setup
    from repro.compress import compress
    art = compress(params, cfg, log=lambda s: None)   # PTQ-only artifact
    ctx = dataclasses.replace(default_ctx(), quantized_kv=True)
    prompts = _prompts(cfg, [9, 14], seed=3)
    eng = Engine(art.params, cfg, ctx=ctx, n_slots=2, max_seq=64,
                 sched=SchedulerConfig(prefill_chunk=4))
    res = eng.run([Request(prompt=p, max_new_tokens=5) for p in prompts])
    for uid, prompt in enumerate(prompts):
        ref = serial_decode(art.params, cfg, prompt, 5, ctx=ctx, max_seq=64)
        assert res[uid].tokens == ref


def test_xlstm_engine_matches_serial_token_identical():
    """Fully recurrent config (xLSTM mLSTM/sLSTM blocks, zero attention
    layers): every pool entry keeps its slot axis and routes through the
    ``is_kv_entry == False`` branch of the slot gather/scatter — the
    discriminator path that KV-centric configs never touch. Engine output
    must still equal serial decode token-for-token, with staggered
    arrivals and chunked prefill interleaving decode."""
    cfg = configs.get_smoke_config("xlstm-1.3b")
    params = lm.init_params(jax.random.PRNGKey(1), cfg)
    # the whole pool must be recurrent state: no entry may look like KV
    pool = sp.init_pool(cfg, 2, 64, default_ctx(), params=params)
    assert pool["caches"] and all(not sp.is_kv_entry(e)
                                  for e in pool["caches"])
    _assert_staggered_engine_matches_serial(cfg, params)


def test_hybrid_paged_engine_matches_serial_token_identical():
    """A period mixing Mamba and attention blocks, paged, over three layer
    groups: each attention arena rides the layer scan's carry and is read
    and written by layer index, while the Mamba state is sliced per group
    through ``xs``. The split must keep every entry with its own layer:
    engine output equals serial (contiguous) decode token-for-token. The
    experts are left out: with them the engine already differs from serial
    decode, paged or not (ROADMAP, experts in the engine)."""
    smoke = configs.get_smoke_config("jamba-1.5-large-398b")
    cfg = dataclasses.replace(smoke, n_layers=3 * smoke.n_layers,
                              block_pattern=smoke.block_pattern * 3,
                              moe=None)
    params = lm.init_params(jax.random.PRNGKey(1), cfg)
    eng = _assert_staggered_engine_matches_serial(cfg, params, page_size=8,
                                                  prefix_cache=False)
    assert [sp.is_kv_entry(e) for e in eng.pool["caches"]] == [False, True]
    assert lm.pattern_period(cfg) == 2


def _assert_staggered_engine_matches_serial(cfg, params, **engine_kw):
    """Three requests arriving at ticks 0, 2, 4 on two slots, chunked
    prefill interleaving decode: each one's tokens equal serial decode's."""
    prompts = _prompts(cfg, [11, 6, 17], seed=5)
    eng = Engine(params, cfg, n_slots=2, max_seq=64,
                 sched=SchedulerConfig(prefill_chunk=5), **engine_kw)
    res = eng.run([Request(prompt=p, max_new_tokens=6) for p in prompts],
                  arrival_ticks=[0, 2, 4])
    for idx, prompt in enumerate(prompts):
        # recurrent leaves carry the batch on axis 1 too (decode_rows)
        ref = serial_decode(params, cfg, prompt, 6, max_seq=64,
                            decode_rows=eng.n_slots)
        assert res[idx].tokens == ref, (idx, res[idx].tokens, ref)
    return eng


# ------------------------------------------------------------------ pool ops
def test_state_pool_gather_scatter_roundtrip(setup):
    cfg, params = setup
    ctx = default_ctx()
    pool = sp.init_pool(cfg, 3, 32, ctx, params=params)
    assert pool["pos"].shape == (3,)
    single = sp.init_slot_template(cfg, 32, ctx, params=params)
    # run one real prefill into the template, scatter to slot 1, gather back
    toks = np.arange(8, dtype=np.int32)[None]
    _, filled = lm.decode_step(params, cfg, single, jax.numpy.asarray(toks),
                               ctx)
    pool2 = sp.scatter_slot(pool, 1, filled)
    back = sp.gather_slot(pool2, 1)
    assert int(back["pos"]) == 8
    a = jax.tree_util.tree_leaves(back["caches"])
    b = jax.tree_util.tree_leaves(filled["caches"])
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # other slots untouched
    other = sp.gather_slot(pool2, 0)
    assert int(other["pos"]) == 0


def test_submit_validates_budget(setup):
    cfg, params = setup
    eng = Engine(params, cfg, n_slots=1, max_seq=16)
    with pytest.raises(ValueError):
        eng.submit(Request(prompt=list(range(12)), max_new_tokens=8))
    with pytest.raises(ValueError):
        eng.submit(Request(prompt=[], max_new_tokens=2))


def test_run_twice_keeps_staggered_arrivals(setup):
    """arrival_ticks are relative to each run's start: a reused engine (the
    bench warmup pattern) must not collapse the second run into a burst."""
    cfg, params = setup
    eng = Engine(params, cfg, n_slots=2, max_seq=64)
    reqs = [Request(prompt=p, max_new_tokens=2)
            for p in _prompts(cfg, [6, 6], seed=4)]
    arrivals = [0, 500]        # req 1 arrives long after req 0 finished
    assert len(eng.run(reqs, arrival_ticks=arrivals)) == 2
    ticks_after_warmup = eng.ticks
    assert ticks_after_warmup >= 500
    # second run: if arrivals were compared against absolute engine ticks,
    # both requests would admit instantly at its start
    results = eng.run(reqs, arrival_ticks=arrivals)
    assert len(results) == 2
    assert all(len(r.tokens) == 2 for r in results.values())
    # with a 500-tick gap and 2-token requests, the engine must go idle
    # between them: total ticks advance by >= 500 again
    assert eng.ticks - ticks_after_warmup >= 500


# ------------------------------------------------------------ multi-step
def test_multi_step_decode_token_identical_and_fewer_syncs(setup):
    """decode_steps=8: EOS and length stops land mid-scan (max_new_tokens=6
    is not a multiple of 8), outputs stay token-identical to serial decode,
    and the host syncs far less often than it runs device decode steps."""
    cfg, params = setup
    prompts = _prompts(cfg, [9, 5, 14], seed=7)
    # make request 0 stop via EOS partway through its budget
    eos_tok = serial_decode(params, cfg, prompts[0], 3, max_seq=64)[2]
    eng = Engine(params, cfg, n_slots=3, max_seq=64,
                 sched=SchedulerConfig(prefill_chunk=4, decode_steps=8))
    reqs = [Request(prompt=prompts[0], max_new_tokens=6, eos_id=eos_tok),
            Request(prompt=prompts[1], max_new_tokens=6),
            Request(prompt=prompts[2], max_new_tokens=6)]
    results = eng.run(reqs)
    for i, req in enumerate(reqs):
        ref = serial_decode(params, cfg, req.prompt, req.max_new_tokens,
                            max_seq=64, eos_id=req.eos_id)
        assert results[i].tokens == ref, (i, results[i].tokens, ref)
    assert results[0].finish_reason == "eos"
    assert eng.stats["device_steps"] == 8 * eng.stats["decode_ticks"]
    # the whole point: decode tokens arrive in far fewer syncs than steps
    assert eng.stats["host_syncs"] < eng.stats["device_steps"]
    assert eng.stats["decode_slot_steps"] <= eng.stats["device_steps"] * 3


def test_decode_steps_one_matches_multi(setup):
    """decode_steps=1 (the legacy per-token-sync regime) and the default
    multi-step loop must produce identical tokens for identical loads."""
    cfg, params = setup
    prompts = _prompts(cfg, [7, 11], seed=8)
    outs = []
    for ds in (1, 4):
        eng = Engine(params, cfg, n_slots=2, max_seq=64,
                     sched=SchedulerConfig(prefill_chunk=4, decode_steps=ds))
        res = eng.run([Request(prompt=p, max_new_tokens=5) for p in prompts])
        outs.append({i: r.tokens for i, r in res.items()})
    assert outs[0] == outs[1]


# --------------------------------------------------------- window debugging
def test_undersized_prefill_window_caught_by_debug_check(setup, monkeypatch):
    """A host caller that miscomputes the static window silently attends a
    truncated cache and emits wrong tokens — REPRO_DEBUG_WINDOW=1 must turn
    that into an immediate host-side error before the prefill dispatch."""
    cfg, params = setup
    eng = Engine(params, cfg, n_slots=1, max_seq=64,
                 sched=SchedulerConfig(prefill_chunk=8))
    monkeypatch.setenv("REPRO_DEBUG_WINDOW", "1")
    # sabotage: fixed 8-wide window, too small once prefill passes chunk 1
    monkeypatch.setattr(eng.scheduler, "visible_window",
                        lambda needed, max_seq: 8)
    eng.submit(Request(prompt=list(range(1, 13)), max_new_tokens=2))
    with pytest.raises(AssertionError, match="undersized visible window"):
        while eng.has_work:
            eng.step()


def test_undersized_decode_window_caught_by_debug_check(setup, monkeypatch):
    """Same guard on the decode dispatch: an 8-token prompt prefills fine
    under a pinned 8-wide window, but the first decode step needs
    pos + decode_steps = 12 visible positions."""
    cfg, params = setup
    eng = Engine(params, cfg, n_slots=1, max_seq=64,
                 sched=SchedulerConfig(prefill_chunk=8, decode_steps=4))
    monkeypatch.setenv("REPRO_DEBUG_WINDOW", "1")
    monkeypatch.setattr(eng.scheduler, "visible_window",
                        lambda needed, max_seq: 8)
    eng.submit(Request(prompt=list(range(1, 9)), max_new_tokens=4))
    with pytest.raises(AssertionError, match="undersized visible window"):
        while eng.has_work:
            eng.step()


def test_debug_window_check_passes_on_correct_windows(setup, monkeypatch):
    """With the real scheduler the armed check must never fire, and outputs
    stay token-identical to serial decode."""
    cfg, params = setup
    monkeypatch.setenv("REPRO_DEBUG_WINDOW", "1")
    prompts = _prompts(cfg, [9, 17], seed=6)
    eng = Engine(params, cfg, n_slots=2, max_seq=64,
                 sched=SchedulerConfig(prefill_chunk=5))
    res = eng.run([Request(prompt=p, max_new_tokens=4) for p in prompts])
    for i, p in enumerate(prompts):
        assert res[i].tokens == serial_decode(params, cfg, p, 4, max_seq=64)


def test_summarize_results_empty():
    """A zero-request result set must summarize to zeros, not IndexError."""
    from repro.serving import summarize_results
    s = summarize_results({}, wall_s=1.0)
    assert s["n_requests"] == 0 and s["tokens_per_s"] == 0.0
    assert s["latency_p95_ms"] == 0.0 and s["ttft_p50_ms"] == 0.0


# ----------------------------------------------------------------- launcher
def test_load_artifact_serves_without_calibration(setup, tmp_path,
                                                  monkeypatch):
    """`serve --load-artifact` must never re-run sensitivity/calibration:
    a saved artifact already paid for its Fisher pass."""
    cfg, params = setup
    from repro.compress import compress
    from repro.launch import serve
    from repro.launch.checkpoint import save_artifact
    art = compress(params, cfg, log=lambda s: None)
    save_artifact(str(tmp_path / "art"), art)

    import repro.core.sensitivity as sens

    def _boom(*a, **k):
        raise AssertionError("calibration ran on the --load-artifact path")

    monkeypatch.setattr(sens, "fisher_diag", _boom)
    serve.main(["--smoke", "--load-artifact", str(tmp_path / "art"),
                "--batch", "2", "--prompt-len", "8", "--tokens", "4"])


def test_serve_engine_trace_replay(setup, tmp_path):
    """`serve --engine --trace` replays a JSONL trace and self-verifies
    against serial decode (the CI acceptance path)."""
    import json
    from repro.launch import serve
    trace = tmp_path / "trace.jsonl"
    lines = [{"arrival_s": 0.0, "prompt_len": 9, "max_new_tokens": 4},
             {"arrival_s": 0.01, "prompt_len": 5, "max_new_tokens": 4},
             {"arrival_s": 0.02, "prompt_len": 12, "max_new_tokens": 4}]
    trace.write_text("\n".join(json.dumps(d) for d in lines) + "\n")
    stats = serve.main(["--smoke", "--engine", "--trace", str(trace),
                        "--engine-slots", "2", "--prefill-chunk", "4",
                        "--max-seq", "32", "--verify"])
    assert stats["n_requests"] == 3
    assert stats["out_tokens"] == 12
    assert stats["tokens_per_s"] > 0


def _faulty_build_engine(monkeypatch, serve):
    """serve's engines fail their first decode dispatch."""
    from repro.serving import faults
    real = serve.build_engine

    def build(*a, **k):
        eng = real(*a, **k)
        faults.inject_decode_fault(eng, at=1)
        return eng
    monkeypatch.setattr(serve, "build_engine", build)
    return faults.InjectedFault


def test_serve_engine_exits_nonzero_on_errored_request(setup, tmp_path,
                                                       monkeypatch):
    """Fault isolation keeps the engine serving, but a trace replay in
    which a request finished ``"error"`` is a failed run, with the fault
    as its cause."""
    import json
    from repro.launch import serve
    fault = _faulty_build_engine(monkeypatch, serve)
    trace = tmp_path / "trace.jsonl"
    trace.write_text(json.dumps({"prompt_len": 6, "max_new_tokens": 4}))
    with pytest.raises(SystemExit, match="finished with an error") as e:
        serve.main(["--smoke", "--engine", "--trace", str(trace),
                    "--max-seq", "32"])
    assert isinstance(e.value.__cause__, fault)


def test_serve_http_warmup_fault_exits_before_listening(setup, monkeypatch):
    """The HTTP warmup checks its own result before zeroing the stats: a
    warmup fault fails the server instead of vanishing."""
    from repro.launch import serve
    _faulty_build_engine(monkeypatch, serve)

    def never(*a, **k):
        raise AssertionError("the listener opened after a failed warmup")
    monkeypatch.setattr("repro.serving.service.run_http", never)
    with pytest.raises(SystemExit, match="warmup"):
        serve.main(["--smoke", "--http", "--port", "0", "--max-seq", "32"])


def test_serve_profiler_failure_is_fatal(setup, tmp_path, monkeypatch):
    """``--profile-dir`` with a profiler that cannot start fails the run."""
    import json
    from repro.launch import serve

    def broken(*a, **k):
        raise RuntimeError("profiler unavailable")
    monkeypatch.setattr(jax.profiler, "trace", broken)
    trace = tmp_path / "trace.jsonl"
    trace.write_text(json.dumps({"prompt_len": 6, "max_new_tokens": 2}))
    with pytest.raises(RuntimeError, match="profiler unavailable"):
        serve.main(["--smoke", "--engine", "--trace", str(trace),
                    "--max-seq", "32", "--profile-dir",
                    str(tmp_path / "prof")])


def test_compile_cache_dir_rule(monkeypatch):
    """The persistent compile cache goes where ``JAX_COMPILATION_CACHE_DIR``
    says, with no other directory set in code; without it, to one fixed,
    git-ignored directory inside the checkout."""
    import pathlib
    from repro.launch.compile_cache import CHECKOUT_CACHE_DIR, use_compile_cache
    prev = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert use_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert use_compile_cache() == use_compile_cache() \
            == str(CHECKOUT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE_DIR)
        root = pathlib.Path(__file__).resolve().parents[1]
        assert CHECKOUT_CACHE_DIR.parent == root
        assert f"{CHECKOUT_CACHE_DIR.name}/" in (root / ".gitignore").read_text()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
