"""Benchmark harness — one function per paper table/figure + system benches.

Prints ``name,us_per_call,derived`` CSV rows (derived = the table's headline
metric). Heavy artifacts (the CNN HQP experiment, the dry-run roofline cells)
are read from experiments/ when present; otherwise a reduced inline version
runs so this module is always executable on a bare CPU container.

  Table I   (MobileNetV3 HQP vs Q8 vs P50)  -> bench_table1_mobilenetv3
  Table II  (ResNet-18 HQP vs Q8)           -> bench_table2_resnet18
  SIII-C    (C_HQP vs C_QAT complexity)     -> bench_complexity_analysis
  SV-C      (layer-wise non-uniform theta)  -> bench_layerwise_sparsity
  SV-E      (energy ratio == speedup)       -> bench_energy
  Fig. 2/3 analogue (LM fleet)              -> bench_lm_hqp_serving
  continuous-batching engine                -> bench_serving
  self-speculative (HQP drafts, bf16 checks)-> bench_speculative
  paged KV + shared-prefix reuse            -> bench_paged
  HTTP/SSE front door + overload sweep      -> bench_http
  fault injection + feasibility admission   -> bench_chaos
  decode attention (windowed vs full)       -> bench_decode_attention
  prefill attention (kernel vs einsum)      -> bench_prefill_attention
  kernels                                   -> bench_kernels
  SRoofline                                 -> bench_roofline_table

CLI:
  python benchmarks/run.py                          # everything, CSV rows
  python benchmarks/run.py --only serving,kernels \
      --json BENCH_pr.json                          # CI perf-trajectory mode

``bench_serving`` additionally writes BENCH_serving.json (tokens/s + latency
percentiles per variant); ``--json`` wraps all emitted rows plus the serving
payload into one schema-tagged file CI validates and uploads.
"""
from __future__ import annotations

import argparse
import asyncio
import gc
import json
import pathlib
import time
from typing import List, Tuple

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
REPRO_DIR = ROOT / "experiments" / "repro"
DRYRUN_DIR = ROOT / "experiments" / "dryrun"

BENCH_SCHEMA = "repro-bench/v1"
SERVING_SCHEMA = "repro-bench-serving/v1"

Row = Tuple[str, float, str]

# last bench_serving payload, picked up by --json (benches keep the uniform
# "returns rows" signature)
_LAST_SERVING: dict = {}


def _timed_min(fn, args, reps: int) -> float:
    """Warmup + min-of-reps timing for the gated attention benches.

    Min, not median: the flatness/ratio gates drive CI, and on shared
    runners scheduler noise only ever ADDS time — the minimum is the stable
    estimate of the true cost."""
    import jax
    jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.min(ts))


def _load_or_run_cnn(arch: str) -> dict:
    f = REPRO_DIR / f"{arch}.json"
    if f.exists():
        return json.loads(f.read_text())
    from repro.repro_exp.cnn_experiment import run_experiment
    table = run_experiment(arch, train_steps=150, n_train=2000, n_val=800,
                           n_calib=400, log=lambda s: None)
    REPRO_DIR.mkdir(parents=True, exist_ok=True)
    f.write_text(json.dumps(table, indent=1))
    return table


def _cnn_rows(table: dict, tag: str) -> List[Row]:
    rows = []
    for r in table["rows"]:
        sp_model = table["speedups_modeled"][r["method"]]
        name = r["method"].replace(" ", "_").replace("(", "").replace(")", "")
        rows.append((
            f"{tag}/{name}",
            r["measured_ms"] * 1000,
            f"speedup={sp_model:.2f}x size_red={r['size_reduction']:.0%} "
            f"drop={r['drop']*100:.2f}pct theta={r['theta']:.0%} "
            f"compliant={r['compliant']}"))
    return rows


def bench_table1_mobilenetv3() -> List[Row]:
    return _cnn_rows(_load_or_run_cnn("mobilenetv3s"), "table1_mbv3")


def bench_table2_resnet18() -> List[Row]:
    return _cnn_rows(_load_or_run_cnn("resnet18"), "table2_resnet18")


def bench_complexity_analysis() -> List[Row]:
    """C_HQP = N_calib*C_grad + T_prune*N_val*C_inf  vs  C_QAT (SIII-C)."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    from repro.configs import get_cnn_config
    from repro.models import cnn
    from repro.repro_exp.cnn_experiment import ce_loss
    cfg = dataclasses.replace(get_cnn_config("mobilenetv3s"), width_mult=0.5)
    v = cnn.cnn_init(jax.random.PRNGKey(0), cfg)
    x = {"image": jnp.zeros((100, 32, 32, 3)),
         "label": jnp.zeros((100,), jnp.int32)}
    grad = jax.jit(jax.grad(lambda p, b: ce_loss(
        cfg, {"params": p, "stats": v["stats"]}, b)[0]))
    inf = jax.jit(lambda vv, b: cnn.cnn_apply(cfg, vv, b["image"])[0])
    grad(v["params"], x)
    inf(v, x)

    def t(f, *a):
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(f(*a))
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts))

    c_grad, c_inf = t(grad, v["params"], x), t(inf, v, x)
    n_calib, n_val, n_train, t_prune, epochs = 5000, 5000, 1_281_167, 45, 5
    c_hqp = n_calib / 100 * c_grad + t_prune * (n_val / 100) * c_inf
    c_qat = epochs * n_train / 100 * c_grad
    return [
        ("complexity/C_grad_per_100", c_grad * 1e6, "forward-backward"),
        ("complexity/C_inf_per_100", c_inf * 1e6, "inference"),
        ("complexity/C_HQP", c_hqp * 1e6, f"{c_hqp:.0f}s-equivalent"),
        ("complexity/C_QAT", c_qat * 1e6,
         f"QAT/HQP={c_qat / c_hqp:.0f}x (paper: orders of magnitude)"),
    ]


def bench_layerwise_sparsity() -> List[Row]:
    """SV-C: non-uniform theta across depth."""
    table = _load_or_run_cnn("mobilenetv3s")
    fam = table["hqp_sparsity_by_family"]
    # manifest format stores θ floats; pre-artifact caches stored info dicts
    thetas = {k: (v["theta"] if isinstance(v, dict) else v)
              for k, v in fam.items()}
    if not thetas:
        return [("layerwise/none", 0.0, "no families")]
    mx = max(thetas, key=thetas.get)
    mn = min(thetas, key=thetas.get)
    return [
        ("layerwise/max_theta", 0.0, f"{mx}={thetas[mx]:.0%}"),
        ("layerwise/min_theta", 0.0, f"{mn}={thetas[mn]:.0%}"),
        ("layerwise/spread", 0.0,
         f"nonuniform={thetas[mx] - thetas[mn]:.0%}"),
    ]


def bench_energy() -> List[Row]:
    """SV-E: E = P*L  =>  energy ratio == speedup (identity check)."""
    table = _load_or_run_cnn("mobilenetv3s")
    sp = table["speedups_modeled"]["Proposed HQP"]
    return [("energy/ratio_equals_speedup", 0.0,
             f"E_FP32/E_HQP={sp:.2f}x==speedup")]


def bench_lm_hqp_serving() -> List[Row]:
    """LM-fleet analogue of Tables I/II: decode us/token + size reduction,
    with the INT8 row served from the typed ``compress()`` artifact."""
    import dataclasses as dc
    import jax
    import jax.numpy as jnp
    from repro import configs
    from repro.compress import compress
    from repro.core.pipeline import HQPConfig
    from repro.core.pruning import param_bytes
    from repro.models import lm
    from repro.sharding.ctx import default_ctx
    cfg = configs.get_smoke_config("granite-3-8b")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    art = compress(params, cfg, hqp=HQPConfig(weight_granularity="channel"),
                   log=lambda s: None)
    rows = [("lm_serving/manifest", 0.0,
             f"bytes={art.manifest.bytes_before}->{art.manifest.bytes_after} "
             f"qfrac={art.manifest.quantized_fraction:.2f} "
             f"theta={art.manifest.theta:.2f}")]
    for name, p, qkv in [("bf16", params, False),
                         ("hqp_int8", art.params, True)]:
        ctx = dc.replace(default_ctx(), quantized_kv=qkv)
        state = lm.init_decode_state(cfg, 4, 64, ctx,
                                     params=p if qkv else None)
        tok = jnp.zeros((4, 1), jnp.int32)
        step = jax.jit(lambda pp, s, t: lm.decode_step(pp, cfg, s, t, ctx))
        logits, state = step(p, state, tok)
        jax.block_until_ready(logits)
        ts = []
        for _ in range(10):
            t0 = time.perf_counter()
            logits, state = step(p, state, tok)
            jax.block_until_ready(logits)
            ts.append(time.perf_counter() - t0)
        rows.append((f"lm_serving/{name}", float(np.median(ts)) * 1e6,
                     f"size={param_bytes(p)/1e6:.1f}MB"))
    return rows


def bench_serving(out_path: str = "BENCH_serving.json") -> List[Row]:
    """Continuous-batching engine throughput + latency percentiles, bf16 vs
    the INT8 HQP artifact — the serving-regime numbers CI tracks per PR.

    The ``bf16_sync1`` variant pins ``decode_steps=1`` (the PR-2 per-token
    host-sync behavior) against the default multi-step device decode loop, so
    the host-sync amortization shows up as a tokens/s delta in the same file;
    every variant also records ``host_syncs``/``device_steps`` so the win is
    observable, not inferred."""
    import dataclasses as dc
    import jax
    from repro import configs
    from repro.compress import compress
    from repro.core.pruning import param_bytes
    from repro.models import lm
    from repro.serving import (Engine, Request, SchedulerConfig,
                               summarize_results)
    from repro.sharding.ctx import default_ctx

    cfg = configs.get_smoke_config("qwen3-0.6b")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    art = compress(params, cfg, log=lambda s: None)
    rng = np.random.RandomState(0)
    n_req, new_tok, n_slots, chunk, dsteps = 8, 16, 4, 8, 4
    prompts = [rng.randint(0, cfg.vocab_size, 8 + (5 * i) % 13).tolist()
               for i in range(n_req)]

    payload = _serving_payload(cfg, n_req, n_slots, chunk, new_tok, dsteps)
    rows: List[Row] = []
    for name, p, qkv, ds in [("bf16", params, False, dsteps),
                             ("bf16_sync1", params, False, 1),
                             ("hqp_int8", art.params, True, dsteps)]:
        ctx = dc.replace(default_ctx(), quantized_kv=qkv)
        eng = Engine(p, cfg, ctx=ctx, n_slots=n_slots, max_seq=64,
                     sched=SchedulerConfig(prefill_chunk=chunk,
                                           decode_steps=ds))
        reqs = [Request(prompt=pr, max_new_tokens=new_tok) for pr in prompts]
        arrivals = [2 * i for i in range(n_req)]
        results, wall = _timed_engine_run(eng, reqs, arrivals)
        v = {
            **summarize_results(results, wall),
            "param_bytes": int(param_bytes(p)),
            "decode_ticks": eng.stats["decode_ticks"],
            "prefill_ticks": eng.stats["prefill_ticks"],
            "decode_steps": ds,
            "host_syncs": eng.stats["host_syncs"],
            "device_steps": eng.stats["device_steps"],
        }
        if name == "hqp_int8":
            v["artifact_bytes"] = art.manifest.bytes_after
            v["bytes_before"] = art.manifest.bytes_before
        payload["variants"][name] = v
        payload["expected_variants"].append(name)
        rows.append((f"serving/{name}", wall / max(v["out_tokens"], 1) * 1e6,
                     f"tok_s={v['tokens_per_s']:.1f} "
                     f"p50={v['latency_p50_ms']:.0f}ms "
                     f"p95={v['latency_p95_ms']:.0f}ms "
                     f"syncs={v['host_syncs']} dsteps={v['device_steps']} "
                     f"bytes={v['param_bytes']}"))

    if out_path:
        pathlib.Path(out_path).write_text(json.dumps(payload, indent=1))
    return rows


def _serving_payload(cfg, n_req, n_slots, chunk, new_tok, dsteps) -> dict:
    """The shared BENCH_serving.json payload: ``bench_serving`` and
    ``bench_speculative`` both merge their variants into ``_LAST_SERVING``
    (and re-write the file), so one schema-tagged document carries the full
    bf16 / int8 / speculative comparison regardless of which benches a
    ``--only`` subset selected. ``expected_variants`` records every variant
    a bench in this process INTENDED to produce — ``check_bench`` fails
    with a named-variant message if one is missing from the final file."""
    global _LAST_SERVING
    if not _LAST_SERVING:
        _LAST_SERVING = {"schema": SERVING_SCHEMA, "arch": cfg.name,
                         "n_requests": n_req, "n_slots": n_slots,
                         "prefill_chunk": chunk, "max_new_tokens": new_tok,
                         "decode_steps": dsteps, "variants": {},
                         "expected_variants": []}
    _LAST_SERVING.setdefault("expected_variants", [])
    return _LAST_SERVING


def _timed_engine_run(eng, reqs, arrivals, best_of: int = 2):
    """Warmup run (compiles every tail-chunk shape and window bucket), then
    ``best_of`` timed runs keeping the fastest — shared-runner noise only
    ever ADDS time, and the serving gates compare variants against each
    other. Returns (results, wall_s) from the fastest run; ``eng.stats``
    holds exactly one run's counters (zeroed before each timed run)."""
    eng.run(reqs, arrival_ticks=arrivals)
    best = None
    for _ in range(best_of):
        for k in eng.stats:
            eng.stats[k] = 0
        t0 = time.perf_counter()
        results = eng.run(reqs, arrival_ticks=arrivals)
        wall = time.perf_counter() - t0
        if best is None or wall < best[1]:
            best = (results, wall)
    return best


def bench_speculative(out_path: str = "BENCH_serving.json") -> List[Row]:
    """Self-speculative serving (HQP artifact drafts, bf16 parent verifies)
    vs the bf16 ``decode_steps=4`` baseline it must beat, CI-gated by
    ``check_bench``:

      * ``acceptance_rate`` (accepted drafts / drafted tokens, straight
        from ``Engine.stats``) must clear the 0.7 floor — HQP's Δacc bound
        is what makes the compressed artifact a high-acceptance drafter,
        so acceptance IS the quality-vs-speed headline (Ps&Qs: quantization
        as a latency tool);
      * speculative tokens/s must beat the ``spec_baseline`` variant —
        greedy speculative output is bit-identical to serial bf16, so that
        delta is free wall-clock, not a quality trade.

    The workload is SINGLE-STREAM and DECODE-HEAVY (one slot, 48 generated
    tokens per request) — the paper's ultra-low-latency edge regime, and
    speculation's: at batch 1 a multi-position verify pass costs about one
    decode step (op overhead dominates, measured flat in Sq), so k drafts
    + 1 verify buy up to k+1 tokens for ~k+1 invocation-equivalents of the
    CHEAPER drafter, and the per-request p50 latency drops ~2x. (At batch
    4 the verify pass scales with Sq on CPU and the advantage shrinks to
    ~parity — the batched numbers stay visible in ``bench_serving``.)
    Fairness guards: the baseline runs the SAME prompts/arrivals/slots/
    chunking under the default ``decode_steps=4`` scan, and both engines
    are timed in interleaved passes (min per engine) so machine drift
    during the bench cannot bias the ratio — the same discipline as
    ``bench_prefill_attention``."""
    import dataclasses as dc
    import jax
    from repro import configs
    from repro.compress import compress
    from repro.core.pruning import param_bytes
    from repro.models import lm
    from repro.serving import (Engine, Request, SchedulerConfig,
                               summarize_results)
    from repro.sharding.ctx import default_ctx

    cfg = configs.get_smoke_config("qwen3-0.6b")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    art = compress(params, cfg, log=lambda s: None)
    rng = np.random.RandomState(0)
    n_req, new_tok, n_slots, chunk = 6, 48, 1, 16
    max_seq, dsteps, spec_k, spec_cycles = 128, 4, 4, 4
    prompts = [rng.randint(0, cfg.vocab_size, 8 + (5 * i) % 13).tolist()
               for i in range(n_req)]
    reqs = [Request(prompt=pr, max_new_tokens=new_tok) for pr in prompts]
    arrivals = [0] * n_req

    payload = _serving_payload(cfg, n_req, n_slots, chunk, new_tok, dsteps)
    rows: List[Row] = []

    ctx_q = dc.replace(default_ctx(), quantized_kv=True)
    base_eng = Engine(params, cfg, ctx=default_ctx(), n_slots=n_slots,
                      max_seq=max_seq,
                      sched=SchedulerConfig(prefill_chunk=chunk,
                                            decode_steps=dsteps))
    spec_eng = Engine(params, cfg, ctx=default_ctx(), n_slots=n_slots,
                      max_seq=max_seq,
                      sched=SchedulerConfig(prefill_chunk=chunk,
                                            decode_steps=dsteps),
                      draft_params=art.params, spec_k=spec_k,
                      spec_cycles=spec_cycles, draft_ctx=ctx_q,
                      draft_manifest=art.manifest)
    best = {}
    for name, eng in (("base", base_eng), ("spec", spec_eng)):
        eng.run(reqs, arrival_ticks=arrivals)      # warmup: compile all
    for _ in range(3):                             # interleaved timed passes
        for name, eng in (("base", base_eng), ("spec", spec_eng)):
            for k in eng.stats:
                eng.stats[k] = 0
            t0 = time.perf_counter()
            results = eng.run(reqs, arrival_ticks=arrivals)
            wall = time.perf_counter() - t0
            if name not in best or wall < best[name][1]:
                best[name] = (results, wall, dict(eng.stats))

    base_res, base_wall, base_stats = best["base"]
    base_sum = summarize_results(base_res, base_wall)
    results, wall, st = best["spec"]
    accept = st["accepted_tokens"] / max(st["drafted_tokens"], 1)
    v = {
        **summarize_results(results, wall),
        "param_bytes": int(param_bytes(params)),
        "artifact_bytes": art.manifest.bytes_after,
        "spec_k": spec_k,
        "spec_cycles": spec_cycles,
        "max_new_tokens": new_tok,
        "decode_ticks": st["decode_ticks"],
        "prefill_ticks": st["prefill_ticks"],
        "host_syncs": st["host_syncs"],
        "device_steps": st["device_steps"],
        "drafted_tokens": st["drafted_tokens"],
        "accepted_tokens": st["accepted_tokens"],
        "acceptance_rate": accept,
        "baseline_tokens_per_s": base_sum["tokens_per_s"],
    }
    v["speedup_vs_bf16"] = (v["tokens_per_s"]
                            / max(base_sum["tokens_per_s"], 1e-9))
    payload["variants"]["speculative"] = v
    # the baseline lives as its own variant: same workload, so the gate
    # compares like with like (bench_serving's "bf16" variant times a
    # different, prefill-heavier workload)
    payload["variants"]["spec_baseline"] = {
        **base_sum,
        "param_bytes": int(param_bytes(params)),
        "decode_steps": dsteps,
        "max_new_tokens": new_tok,
        "host_syncs": base_stats["host_syncs"],
        "device_steps": base_stats["device_steps"],
    }
    payload["expected_variants"] += ["speculative", "spec_baseline"]
    rows.append((
        "serving/speculative", wall / max(v["out_tokens"], 1) * 1e6,
        f"tok_s={v['tokens_per_s']:.1f} accept={accept:.2f} "
        f"speedup_vs_bf16={v['speedup_vs_bf16']:.2f}x k={spec_k} "
        f"c={spec_cycles} syncs={v['host_syncs']} "
        f"drafted={v['drafted_tokens']}"))
    rows.append((
        "serving/spec_baseline",
        base_wall / max(base_sum["out_tokens"], 1) * 1e6,
        f"tok_s={base_sum['tokens_per_s']:.1f} decode_steps={dsteps} "
        f"(same workload as serving/speculative)"))

    if out_path:
        pathlib.Path(out_path).write_text(json.dumps(payload, indent=1))
    return rows


def bench_paged(out_path: str = "BENCH_serving.json") -> List[Row]:
    """Paged KV cache vs the contiguous pool it replaced, CI-gated by
    ``check_bench``:

      * ``paged`` vs ``paged_baseline`` — the SAME no-sharing workload
        (distinct random prompts long enough to cross page boundaries) on a
        page_size=16 engine vs a contiguous engine, timed in interleaved
        passes (min per engine, the ``bench_speculative`` discipline so
        machine drift cannot bias the ratio). Paging is pure bookkeeping —
        same kernels, one extra page-table gather — so paged tokens/s must
        stay >= 0.95x contiguous. page_size == window_block here so both
        engines attend IDENTICAL visible windows at every dispatch and the
        ratio isolates pure indirection cost (a page size above the window
        block additionally rounds windows up to whole pages — a real cost,
        but a window-bucketing effect, measured by the attention sweeps,
        not a page-table one; it vanishes as max_seq/page grows while this
        smoke cache is only 2 pages deep). The prefix cache is OFF because
        this variant measures overhead, not reuse.
      * ``paged_shared`` — the repeated-system-prompt workload paging
        exists for: every request shares a 64-token (one-page) head, so
        after the warmup run populates the hash-keyed prefix cache, every
        timed admission maps the shared page copy-free (refcount++) and
        prefills only the tail. Gates: >= 1 prefix hit, prefilled tokens <
        total prompt tokens, and ``kv_bytes_peak`` <= 0.6x the contiguous
        footprint for the same (n_slots, max_seq) — the arena only holds
        pages that are actually mapped, while a contiguous pool pays
        n_slots * max_seq up front."""
    import jax
    from repro import configs
    from repro.core.pruning import param_bytes
    from repro.models import lm
    from repro.serving import (Engine, Request, SchedulerConfig,
                               summarize_results)

    cfg = configs.get_smoke_config("qwen3-0.6b")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    pbytes = int(param_bytes(params))
    rng = np.random.RandomState(0)
    n_req, new_tok, n_slots, chunk, dsteps = 6, 16, 4, 16, 4
    max_seq, page_size = 128, 64
    parity_ps = 16                    # == window_block: identical windows
    # 96..108-token prompts + 16 generated: every slot's KV spans 7-8
    # pages at the parity page size
    prompts = [rng.randint(0, cfg.vocab_size, 96 + (5 * i) % 13).tolist()
               for i in range(n_req)]
    reqs = [Request(prompt=pr, max_new_tokens=new_tok) for pr in prompts]
    arrivals = [0] * n_req

    payload = _serving_payload(cfg, n_req, n_slots, chunk, new_tok, dsteps)
    rows: List[Row] = []
    sched = SchedulerConfig(prefill_chunk=chunk, decode_steps=dsteps)
    cont_eng = Engine(params, cfg, n_slots=n_slots, max_seq=max_seq,
                      sched=sched)
    paged_eng = Engine(params, cfg, n_slots=n_slots, max_seq=max_seq,
                       sched=sched, page_size=parity_ps, prefix_cache=False)
    # contiguous engines report their static footprint once at init; the
    # timed-run stat zeroing would lose it (paged engines re-track via the
    # page gauges), so snapshot it here
    cont_kv_bytes = cont_eng.stats["kv_bytes_peak"]
    best = {}
    for name, eng in (("cont", cont_eng), ("paged", paged_eng)):
        eng.run(reqs, arrival_ticks=arrivals)      # warmup: compile all
    for _ in range(3):                             # interleaved timed passes
        for name, eng in (("cont", cont_eng), ("paged", paged_eng)):
            for k in eng.stats:
                eng.stats[k] = 0
            t0 = time.perf_counter()
            results = eng.run(reqs, arrival_ticks=arrivals)
            wall = time.perf_counter() - t0
            if name not in best or wall < best[name][1]:
                best[name] = (results, wall, dict(eng.stats))

    for vname, key in (("paged", "paged"), ("paged_baseline", "cont")):
        results, wall, st = best[key]
        v = {
            **summarize_results(results, wall),
            "param_bytes": pbytes,
            "page_size": parity_ps if key == "paged" else 0,
            "decode_steps": dsteps,
            "host_syncs": st["host_syncs"],
            "device_steps": st["device_steps"],
            "kv_bytes_peak": (st["kv_bytes_peak"] if key == "paged"
                              else cont_kv_bytes),
        }
        if key == "paged":
            v.update(pages_peak=st["pages_peak"], prefix_cache=False)
        payload["variants"][vname] = v
        payload["expected_variants"].append(vname)
        rows.append((f"serving/{vname}",
                     wall / max(v["out_tokens"], 1) * 1e6,
                     f"tok_s={v['tokens_per_s']:.1f} "
                     f"p50={v['latency_p50_ms']:.0f}ms "
                     f"p95={v['latency_p95_ms']:.0f}ms "
                     f"page_size={v['page_size']} "
                     f"kv_peak={v['kv_bytes_peak']}"))
    ratio = (payload["variants"]["paged"]["tokens_per_s"]
             / max(payload["variants"]["paged_baseline"]["tokens_per_s"],
                   1e-9))
    rows[-2] = (rows[-2][0], rows[-2][1],
                rows[-2][2] + f" vs_contiguous={ratio:.2f}x")

    # --- shared-prefix workload: one 64-token system prompt, distinct tails
    head = rng.randint(0, cfg.vocab_size, page_size).tolist()
    sh_reqs = [Request(prompt=head
                       + rng.randint(0, cfg.vocab_size, 8 + (3 * i) % 9)
                       .tolist(),
                       max_new_tokens=new_tok) for i in range(n_req)]
    sh_max_seq = 192
    eng = Engine(params, cfg, n_slots=n_slots, max_seq=sh_max_seq,
                 sched=sched, page_size=page_size)
    results, wall = _timed_engine_run(eng, sh_reqs, [0] * n_req)
    st = eng.stats
    prompt_tokens = sum(len(r.prompt) for r in sh_reqs)
    # what a contiguous pool would pin for the same slots/capacity
    contiguous_bytes = eng._kv_page_bytes * n_slots * eng.max_pages
    v = {
        **summarize_results(results, wall),
        "param_bytes": pbytes,
        "page_size": page_size,
        "max_seq": sh_max_seq,
        "prefix_hits": st["prefix_hits"],
        "prefix_hit_tokens": st["prefix_hit_tokens"],
        "bytes_saved": st["bytes_saved"],
        "cow_copies": st["cow_copies"],
        "prefill_tokens": st["prefill_tokens"],
        "prompt_tokens": prompt_tokens,
        "pages_peak": st["pages_peak"],
        "kv_bytes_peak": st["kv_bytes_peak"],
        "contiguous_kv_bytes": contiguous_bytes,
    }
    payload["variants"]["paged_shared"] = v
    payload["expected_variants"].append("paged_shared")
    rows.append((
        "serving/paged_shared", wall / max(v["out_tokens"], 1) * 1e6,
        f"tok_s={v['tokens_per_s']:.1f} hits={v['prefix_hits']} "
        f"prefilled={v['prefill_tokens']}/{prompt_tokens} "
        f"kv_peak={v['kv_bytes_peak']}/{contiguous_bytes} "
        f"({v['kv_bytes_peak'] / contiguous_bytes:.2f}x contiguous)"))

    if out_path:
        pathlib.Path(out_path).write_text(json.dumps(payload, indent=1))
    return rows


async def _sse_request(port: int, body: bytes, delay_s: float = 0.0) -> dict:
    """One streaming client: POST, then read SSE events with a wall-clock
    stamp per event. Returns status + per-token timing raw material.

    The client runs in the server's own event loop, so on this box (one
    CPU core — there is nothing to overlap with anyway) its parse cost
    lands in the measured wall; the hot loop therefore counts token
    frames with C-speed scans over each received segment instead of
    slicing per frame, and JSON-decodes only the final ``done`` frame."""
    if delay_s > 0:
        await asyncio.sleep(delay_s)
    t_send = time.perf_counter()
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write((f"POST /v1/generate HTTP/1.1\r\nHost: b\r\n"
                  f"Content-Length: {len(body)}\r\n\r\n").encode() + body)
    await writer.drain()
    head = await reader.readuntil(b"\r\n\r\n")
    status = int(head.split(b" ")[1])
    rec = {"status": status, "t_send": t_send, "token_times": [],
           "finish_reason": None, "n_tokens": 0, "t_done": None}
    if status == 200:
        buf = bytearray()
        while rec["t_done"] is None:
            chunk = await reader.read(65536)
            if not chunk:
                break
            t_recv = time.perf_counter()
            buf += chunk
            # process only the complete-frame prefix (a frame may straddle
            # the segment boundary); a burst arriving in one segment shares
            # one stamp — exactly what a real client would observe
            i = buf.rfind(b"\n\n")
            if i < 0:
                continue
            complete = bytes(buf[:i + 2])
            del buf[:i + 2]
            n_tok = complete.count(b"event: token")
            if n_tok:
                rec["token_times"].extend([t_recv] * n_tok)
            j = complete.find(b"event: done")
            if j >= 0:
                frame = complete[j:complete.index(b"\n\n", j)]
                d = json.loads(frame.partition(b"data: ")[2])
                rec["t_done"] = t_recv
                rec["finish_reason"] = d["finish_reason"]
                rec["n_tokens"] = d["n_tokens"]
    else:
        await reader.read()                    # consume the error body
    writer.close()
    return rec


def _run_http_phase(eng, queue_depth, deadline_s, bodies, delays):
    """Fresh Service + front door (ephemeral port) on an already-compiled
    engine; fire one client per body at its delay; drain; return
    (svc.stats, client records, wall_s measured send-to-last-done)."""
    from repro.serving.service import HttpFrontDoor, Service, ServiceConfig
    svc = Service(eng, ServiceConfig(queue_depth=queue_depth,
                                     default_deadline_s=deadline_s))
    door = HttpFrontDoor(svc, host="127.0.0.1", port=0)
    # the previous phase's garbage (dead Service/door/streams, the inproc
    # run's result objects) must not bill its collector pauses to THIS
    # phase's timed window
    gc.collect()

    async def go():
        await door.start()
        try:
            t0 = time.perf_counter()
            recs = await asyncio.gather(
                *[_sse_request(door.port, b, d)
                  for b, d in zip(bodies, delays)])
            wall = time.perf_counter() - t0
            return recs, wall
        finally:
            await door.stop(drain=True)

    recs, wall = asyncio.run(go())
    return dict(svc.stats), recs, wall


def _pct(xs, q, scale=1e3):
    return float(np.percentile(xs, q)) * scale if xs else 0.0


def _client_hist(values_s) -> dict:
    """Client-side seconds -> the same fixed-bucket histogram shape the
    engine reports (telemetry.schema.LATENCY_BUCKETS_S), JSON-ready —
    bench_diff compares these across baselines bucket-wise."""
    from repro.telemetry import Histogram, schema
    h = Histogram("client_s", buckets=schema.LATENCY_BUCKETS_S)
    for v in values_s:
        h.observe(v)
    return h.to_dict()


def bench_http(out_path: str = "BENCH_serving.json") -> List[Row]:
    """The engine behind the real HTTP/SSE front door vs the same engine
    driven in-process — BENCH_serving's traffic benchmark, CI-gated by
    ``check_bench``:

      * ``http_stream`` (CLOSED loop): every client connects at once and
        streams to completion — the same all-at-once workload as the
        in-process ``Engine.run`` timed immediately before ON THE SAME
        ENGINE (same compiled fns, so the delta is pure transport).
        Goodput must stay >= 0.9x in-process tokens/s
        (``goodput_ratio``), with zero sheds and zero deadline
        violations; TTFT and inter-token gap p50/p95/p99 are recorded
        from the CLIENT side of the socket — the numbers a user would
        see, not the engine's view.
      * ``http_overload`` (OPEN loop): uniform-arrival sweep at offered
        rates below/at/above the measured capacity knee
        (``inproc_tokens_per_s / max_new_tokens``) against a deliberately
        shallow admission queue. Below the knee the service must meet
        every deadline (zero violations at zero shed); above it the
        bound must actually engage (sheds > 0) — overload degrades into
        429s, not into blown SLOs. Headline percentile keys summarize
        the LOWEST-rate (below-knee) point; ``sweep`` holds every point.
    """
    import jax
    from repro import configs
    from repro.core.pruning import param_bytes
    from repro.models import lm
    from repro.serving import (Engine, Request, SchedulerConfig,
                               summarize_results)

    import dataclasses

    # 4L/d128 rather than the 2L/d64 smoke config: the transport floor is
    # a fixed ~15-20us/token of syscalls + task wakeups (and this box has
    # ONE core, so none of it overlaps compute), and goodput_ratio is
    # compute/(compute + transport) — measured against a toy model whose
    # decode costs ~130us/token it overstates the transport share ~4x vs
    # any real deployment. Both sides of the ratio run this same engine,
    # so the comparison itself stays apples-to-apples.
    cfg = dataclasses.replace(configs.get_smoke_config("qwen3-0.6b"),
                              n_layers=4, d_model=128, d_ff=256)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)
    # decode-heavy enough (96 tokens/stream) that the fixed transport
    # transient — 12 TCP connects + the few staggered-admission steps
    # before slots fill — amortizes out of the goodput ratio; decode_steps=8
    # keeps the per-step transport overhead (loop wakeup + one socket
    # write/read per stream) under the step's compute on a one-core box
    n_req, new_tok, n_slots, chunk, dsteps = 12, 96, 4, 8, 8
    prompts = [rng.randint(0, cfg.vocab_size, 8 + (5 * i) % 13).tolist()
               for i in range(n_req)]
    reqs = [Request(prompt=pr, max_new_tokens=new_tok) for pr in prompts]
    bodies = [json.dumps({"prompt": pr, "max_new_tokens": new_tok}).encode()
              for pr in prompts]

    eng = Engine(params, cfg, n_slots=n_slots, max_seq=128,
                 sched=SchedulerConfig(prefill_chunk=chunk,
                                       decode_steps=dsteps))
    # warm both paths once: engine compiles (tail-chunk shapes, window
    # buckets), then the transport (listener, pump thread, client sockets)
    eng.run(reqs, arrival_ticks=[0] * n_req)
    _run_http_phase(eng, queue_depth=n_req, deadline_s=None, bodies=bodies,
                    delays=[0.0] * n_req)
    pbytes = int(param_bytes(params))

    payload = _serving_payload(cfg, n_req, n_slots, chunk, new_tok, dsteps)
    rows: List[Row] = []

    # --- closed loop: all clients at once, queue deep enough to admit all.
    # The in-process baseline and the HTTP phase run INTERLEAVED, best-of
    # each, so CPU-clock drift between measurement windows cancels out of
    # goodput_ratio instead of masquerading as overhead. Seven iterations:
    # on a one-core box the per-phase wall jitters ~+/-4% (scheduler
    # bursts slow an entire iteration — its inproc AND http phases
    # together), and the best-of floor estimator needs enough samples for
    # both arms' minima to converge or the 0.9x goodput gate flakes on
    # noise alone. goodput_ratio is best-of/best-of, not paired: its two
    # arms respond to scheduler noise ASYMMETRICALLY (the http arm's
    # thread ping-pong amplifies contention the inproc run shrugs off),
    # so pairing would fold that asymmetry in as phantom transport cost,
    # while the minima compare both arms at the box's capable state —
    # which is what a transport-cost floor means.
    in_best = best = None
    for _ in range(7):
        for k in eng.stats:
            eng.stats[k] = 0
        # drain garbage left by earlier benches / the previous iteration
        # OUTSIDE the timed windows: collector pauses hit the
        # allocation-heavy http phases harder than the inproc run, which
        # shows up as a phantom transport cost in goodput_ratio
        gc.collect()
        t0 = time.perf_counter()
        results = eng.run(reqs, arrival_ticks=[0] * n_req)
        iwall = time.perf_counter() - t0
        if in_best is None or iwall < in_best[1]:
            in_best = (results, iwall)
        st, recs, hwall = _run_http_phase(
            eng, queue_depth=n_req, deadline_s=None, bodies=bodies,
            delays=[0.0] * n_req)
        if best is None or hwall < best[2]:
            best = (st, recs, hwall)
    inproc = summarize_results(*in_best)
    st, recs, hwall = best
    done = [r for r in recs if r["finish_reason"] in ("length", "eos")]
    out_tokens = sum(r["n_tokens"] for r in done)
    ttfts = [r["token_times"][0] - r["t_send"] for r in done
             if r["token_times"]]
    lats = [r["t_done"] - r["t_send"] for r in done]
    gaps = [b - a for r in done
            for a, b in zip(r["token_times"], r["token_times"][1:])]
    goodput = out_tokens / max(hwall, 1e-9)
    v = {
        "n_requests": n_req,
        "out_tokens": out_tokens,
        "tokens_per_s": goodput,
        "latency_p50_ms": _pct(lats, 50), "latency_p95_ms": _pct(lats, 95),
        "latency_p99_ms": _pct(lats, 99),
        "ttft_p50_ms": _pct(ttfts, 50), "ttft_p95_ms": _pct(ttfts, 95),
        "ttft_p99_ms": _pct(ttfts, 99),
        "tok_gap_p50_ms": _pct(gaps, 50), "tok_gap_p95_ms": _pct(gaps, 95),
        "tok_gap_p99_ms": _pct(gaps, 99),
        "param_bytes": pbytes,
        "max_new_tokens": new_tok,
        "inproc_tokens_per_s": inproc["tokens_per_s"],
        "goodput_ratio": goodput / max(inproc["tokens_per_s"], 1e-9),
        "ttft_hist": _client_hist(ttfts),
        "latency_hist": _client_hist(lats),
        "completed": len(done),
        "shed": st["shed"],
        "deadline_violations": st["expired"],
    }
    payload["variants"]["http_stream"] = v
    payload["expected_variants"].append("http_stream")
    rows.append((
        "serving/http_stream", hwall / max(out_tokens, 1) * 1e6,
        f"goodput={goodput:.1f}tok_s ({v['goodput_ratio']:.2f}x inproc) "
        f"ttft_p50={v['ttft_p50_ms']:.1f}ms "
        f"gap_p50={v['tok_gap_p50_ms']:.1f}ms shed={st['shed']}"))

    # --- open loop: uniform arrivals swept past the knee, shallow queue
    cap_rps = inproc["tokens_per_s"] / new_tok
    deadline_s = max(1.0, 20 * _pct(lats, 95) / 1e3)
    n_open, overload_depth = 24, 4
    sweep = []
    for mult in (0.35, 1.0, 3.0):
        rate = mult * cap_rps
        ob = [bodies[i % n_req] for i in range(n_open)]
        delays = [i / rate for i in range(n_open)]
        # pass 1 warms the arrival-pattern-specific compiled variants
        # (staggered admission walks decode-window buckets the all-at-once
        # closed loop never hits; a cold ~1s XLA compile mid-phase would
        # freeze admission and shed everything behind it), pass 2 is timed
        for _ in range(2):
            st, recs, owall = _run_http_phase(
                eng, queue_depth=overload_depth, deadline_s=deadline_s,
                bodies=ob, delays=delays)
        odone = [r for r in recs if r["finish_reason"] in ("length", "eos")]
        ottft = [r["token_times"][0] - r["t_send"] for r in odone
                 if r["token_times"]]
        olat = [r["t_done"] - r["t_send"] for r in odone]
        sweep.append({
            "offered_mult": mult,
            "offered_rps": rate,
            "n_offered": n_open,
            "completed": len(odone),
            "shed": st["shed"],
            "shed_rate": st["shed"] / n_open,
            "deadline_violations": st["expired"],
            "goodput_tokens_per_s": (sum(r["n_tokens"] for r in odone)
                                     / max(owall, 1e-9)),
            "ttft_p50_ms": _pct(ottft, 50), "ttft_p95_ms": _pct(ottft, 95),
            "latency_p50_ms": _pct(olat, 50),
            "latency_p95_ms": _pct(olat, 95),
        })
    low = sweep[0]
    v = {
        "n_requests": n_open,
        "out_tokens": low["completed"] * new_tok,
        "tokens_per_s": low["goodput_tokens_per_s"],
        "latency_p50_ms": low["latency_p50_ms"],
        "latency_p95_ms": low["latency_p95_ms"],
        "ttft_p50_ms": low["ttft_p50_ms"], "ttft_p95_ms": low["ttft_p95_ms"],
        "param_bytes": pbytes,
        "max_new_tokens": new_tok,
        "queue_depth": overload_depth,
        "deadline_s": deadline_s,
        "capacity_rps": cap_rps,
        "sweep": sweep,
    }
    payload["variants"]["http_overload"] = v
    payload["expected_variants"].append("http_overload")
    shed_str = "/".join(f"{p['shed']}" for p in sweep)
    viol_str = "/".join(f"{p['deadline_violations']}" for p in sweep)
    rows.append((
        "serving/http_overload", 1e6 / max(cap_rps, 1e-9),
        f"knee={cap_rps:.0f}rps sweep=0.35x/1x/3x shed={shed_str} "
        f"deadline_viol={viol_str} depth={overload_depth}"))

    if out_path:
        pathlib.Path(out_path).write_text(json.dumps(payload, indent=1))
    return rows


def bench_chaos(out_path: str = "BENCH_serving.json") -> List[Row]:
    """Fault-tolerance benchmark, CI-gated by ``check_bench``:

      * ``chaos`` — a fault-free reference run, then the SAME workload on
        the same paged engine with deterministic injectors armed
        (``serving.faults``): a decode-dispatch fault (kills the in-flight
        batch), page-allocator exhaustion (kills one admission), and a
        host-side cancel. Gates: the injectors actually fired
        (``faults`` >= 1), zero leaked pages afterwards, the pump survived
        every fault (``pump_survived``), and every SURVIVING request's
        token stream is BIT-IDENTICAL to its fault-free twin
        (``survivors_identical``) — failure isolation may not perturb
        neighbors' numerics. ``p95_ratio`` (surviving-request p95 vs the
        fault-free p95) is recorded and loosely bounded: survivors usually
        run FASTER (faulted slots free early), so the gate only catches a
        fault-handling stall, not noise.
      * ``admission_feasible`` — a warm ``AdmissionController`` (fed by a
        deadline-free warmup batch) facing a deadline storm where half the
        deadlines are far below the predicted completion time. Gates:
        infeasible requests are shed AT SUBMIT (``shed_infeasible`` >= 1)
        with an honest positive Retry-After, nothing admitted ever blows
        its deadline (``expired`` == 0), and the generous-deadline half
        still completes (``completed`` >= 1) — the predictor must reject
        the impossible without starving the possible."""
    import jax
    from repro import configs
    from repro.core.pruning import param_bytes
    from repro.models import lm
    from repro.serving import (AdmissionController, Engine, Request,
                               SchedulerConfig, Service, ServiceConfig,
                               summarize_results)
    from repro.serving import faults

    cfg = configs.get_smoke_config("qwen3-0.6b")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    pbytes = int(param_bytes(params))
    rng = np.random.RandomState(0)
    n_req, new_tok, n_slots, chunk, dsteps = 8, 16, 4, 8, 4
    prompts = [rng.randint(0, cfg.vocab_size, 8 + (5 * i) % 13).tolist()
               for i in range(n_req)]
    mk_reqs = lambda: [Request(prompt=pr, max_new_tokens=new_tok)
                       for pr in prompts]
    arrivals = [2 * i for i in range(n_req)]

    payload = _serving_payload(cfg, n_req, n_slots, chunk, new_tok, dsteps)
    rows: List[Row] = []
    eng = Engine(params, cfg, n_slots=n_slots, max_seq=64,
                 sched=SchedulerConfig(prefill_chunk=chunk,
                                       decode_steps=dsteps),
                 page_size=8, prefix_cache=False)

    # --- fault-free reference (also the warmup that compiles everything)
    eng.run(mk_reqs(), arrival_ticks=arrivals)
    for k in eng.stats:
        eng.stats[k] = 0
    t0 = time.perf_counter()
    ref = eng.run(mk_reqs(), arrival_ticks=arrivals)
    ref_wall = time.perf_counter() - t0
    ref_sum = summarize_results(ref, ref_wall)

    # --- chaos run: same workload, injectors armed. The decode fault at
    # dispatch 3 fails the then-active batch; the alloc fault fails one
    # later admission; staggered arrivals guarantee survivors exist.
    h_dec = faults.inject_decode_fault(eng, at=3)
    h_alloc = faults.inject_alloc_failure(eng, at=12, times=2)
    for k in eng.stats:
        eng.stats[k] = 0
    pump_survived = 1
    try:
        t0 = time.perf_counter()
        chaos = eng.run(mk_reqs(), arrival_ticks=arrivals)
        chaos_wall = time.perf_counter() - t0
    except Exception:
        pump_survived, chaos, chaos_wall = 0, {}, 0.0
    finally:
        h_dec.restore()
        h_alloc.restore()
    survivors = {i: r for i, r in chaos.items()
                 if r.finish_reason != "error"}
    errors = len(chaos) - len(survivors)
    identical = int(bool(survivors) and all(
        r.tokens == ref[i].tokens for i, r in survivors.items()))

    # --- cancel exercise: free a mid-flight request by hand, then drain —
    # the leak gate below covers this path too
    uid_a = eng.submit(Request(prompt=prompts[0], max_new_tokens=new_tok))
    eng.submit(Request(prompt=prompts[1], max_new_tokens=new_tok))
    for _ in range(3):
        eng.step()
    eng.cancel(uid_a)
    while eng.has_work:
        eng.step()

    surv_sum = summarize_results(survivors, chaos_wall)
    v = {
        **surv_sum,
        "param_bytes": pbytes,
        "faults": eng.stats["faults"],
        "cancelled": eng.stats["cancelled"],
        "injected_decode_faults": h_dec.fired,
        "injected_alloc_faults": h_alloc.fired,
        "errors": errors,
        "survivors": len(survivors),
        "survivors_identical": identical,
        "pump_survived": pump_survived,
        "leaked_pages": eng.alloc.pages_in_use,
        "fault_free_tokens_per_s": ref_sum["tokens_per_s"],
        "fault_free_p95_ms": ref_sum["latency_p95_ms"],
        "p95_ratio": (surv_sum["latency_p95_ms"]
                      / max(ref_sum["latency_p95_ms"], 1e-9)),
    }
    payload["variants"]["chaos"] = v
    payload["expected_variants"].append("chaos")
    rows.append((
        "serving/chaos", chaos_wall / max(surv_sum["out_tokens"], 1) * 1e6,
        f"faults={v['faults']} survivors={v['survivors']}/{len(chaos)} "
        f"identical={identical} leaked_pages={v['leaked_pages']} "
        f"pump_survived={pump_survived} p95_ratio={v['p95_ratio']:.2f}"))

    # --- feasibility admission under a deadline storm
    ctrl = AdmissionController()
    svc = Service(eng, ServiceConfig(queue_depth=n_req),
                  admission=ctrl)
    for pr in prompts:                       # deadline-free warmup batch:
        svc.submit(Request(prompt=pr, max_new_tokens=new_tok))
    while svc.has_work:                      # feeds the throughput EWMAs
        svc.step()
    st0 = dict(svc.stats)
    storm, retry_sample, predicted_sample = [], 0.0, 0.0
    t0 = time.perf_counter()
    for i, pr in enumerate(prompts):
        w = ctrl.work_s(len(pr), new_tok)    # predicted solo service time
        # odd requests get a deadline far below any feasible completion;
        # even ones get a generous one the engine can honor even queued
        dl = 0.2 * w if i % 2 else max(30.0, 50 * w)
        t = svc.submit(Request(prompt=pr, max_new_tokens=new_tok),
                       deadline_s=dl)
        if t is None:
            retry_sample = svc.last_shed.get("retry_after_s") or 0.0
            predicted_sample = svc.last_shed.get("predicted_s") or 0.0
        else:
            storm.append(t)
    while svc.has_work:
        svc.step()
    wall = time.perf_counter() - t0
    dst = {k: svc.stats[k] - st0[k] for k in svc.stats}
    done = [t for t in storm if t.finish_reason in ("length", "eos")]
    v = {
        **summarize_results(dict(enumerate(done)), wall),
        "param_bytes": pbytes,
        "submitted": dst["submitted"],
        "completed": dst["completed"],
        "shed": dst["shed"],
        "shed_infeasible": dst["shed_infeasible"],
        "expired": dst["expired"],
        "retry_after_s_sample": retry_sample,
        "predicted_s_sample": predicted_sample,
        "leaked_pages": eng.alloc.pages_in_use,
    }
    payload["variants"]["admission_feasible"] = v
    payload["expected_variants"].append("admission_feasible")
    rows.append((
        "serving/admission_feasible",
        wall / max(v["out_tokens"], 1) * 1e6,
        f"shed_infeasible={v['shed_infeasible']}/{n_req} "
        f"completed={v['completed']} expired={v['expired']} "
        f"retry_after={retry_sample:.3f}s predicted={predicted_sample:.3f}s"))

    if out_path:
        pathlib.Path(out_path).write_text(json.dumps(payload, indent=1))
    return rows


def bench_decode_attention() -> List[Row]:
    """Decode-attention ms/step vs cache capacity (``max_seq`` sweep).

    The length-aware windowed path (static window fixed while ``max_seq``
    grows 4x) must stay ~flat — ``check_bench`` gates on <= 1.3x smallest->
    largest — while the full-cache masked einsum (the pre-windowing decode
    path) scales linearly and is recorded as the contrast row. Runs the xla
    backend (timed gate) and the Pallas kernel in interpret mode (``ref``,
    correctness-on-CI; its absolute times are interpreter overhead, not
    kernel speed)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops as kops
    from repro.kernels.backend import set_backend

    b, hq, hkv, hd = 4, 8, 4, 64
    window = 64                      # live-length bucket, fixed across sweep
    sweep = (128, 256, 512)          # 4x capacity growth
    key = jax.random.PRNGKey(0)
    rows: List[Row] = []

    for backend, reps in (("xla", 50), ("ref", 5)):
        for max_seq in sweep:
            ks = jax.random.split(jax.random.fold_in(key, max_seq), 3)
            q = jax.random.normal(ks[0], (b, 1, hq, hd), jnp.bfloat16)
            cache = {
                "k": jax.random.normal(ks[1], (b, max_seq, hkv, hd),
                                       jnp.bfloat16),
                "v": jax.random.normal(ks[2], (b, max_seq, hkv, hd),
                                       jnp.bfloat16),
            }
            start = jnp.full((b,), window - 1, jnp.int32)
            prev = set_backend(backend)
            try:
                win_fn = jax.jit(lambda q, c, s: kops.decode_attention(
                    q, c, s, window=window))
                t_win = _timed_min(win_fn, (q, cache, start), reps)
                rows.append((f"decode_attention/{backend}_win/S{max_seq}",
                             t_win * 1e6, f"window={window} slots={b}"))
                if backend == "xla":
                    full_fn = jax.jit(lambda q, c, s: kops.decode_attention(
                        q, c, s, window=None))
                    t_full = _timed_min(full_fn, (q, cache, start), reps)
                    rows.append((f"decode_attention/xla_full/S{max_seq}",
                                 t_full * 1e6,
                                 f"window=None ratio={t_full/t_win:.2f}x"))
            finally:
                set_backend(prev)
    return rows


def bench_prefill_attention() -> List[Row]:
    """Prefill-attention ms/chunk vs cache capacity (``max_seq`` sweep).

    The backend ``prefill_attention`` primitive under the engine's real
    chunked-admission regime (chunk=16 queries, static window fixed while
    ``max_seq`` grows 4x) vs the einsum paths — the TTFT driver HALP argues
    must be measured under chunking, not inferred from whole-prompt numbers.
    ``xla_einsum`` is the WINDOWED masked einsum, i.e. exactly the PR-3
    engine prefill hot path the primitive replaced — the honest gate
    baseline (a full-cache baseline would flatter the primitive ~2x at the
    smallest sweep point); ``xla_einsum_full`` is the un-windowed einsum,
    recorded as the length-unaware contrast like decode's. ``check_bench``
    gates the xla rows: primitive <= 1.1x the windowed einsum, and <= 1.3x
    flat smallest->largest. The ``ref`` rows run the
    Pallas cache-continuation kernel in interpret mode — correctness
    trajectory only; absolute times there are interpreter overhead, not
    kernel speed, so check_bench ignores them. Both gated operands are
    window-fixed, so every sweep point measures the same comparison and
    check_bench judges the ratio at the least-noisy (minimum-ratio) point;
    the sweep here is likewise timed in two interleaved passes so machine
    drift hits all points alike."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops as kops
    from repro.kernels.backend import set_backend

    b, hq, hkv, hd = 4, 8, 4, 64
    chunk = 16                       # queries per prefill dispatch
    window = 64                      # live-length bucket, fixed across sweep
    sweep = (128, 256, 512)          # 4x capacity growth
    key = jax.random.PRNGKey(1)
    rows: List[Row] = []

    for backend, reps in (("xla", 50), ("ref", 3)):
        prev = set_backend(backend)
        try:
            # build + warm every timed fn for the whole sweep FIRST, then
            # time in two interleaved passes taking the per-point min:
            # slow-machine drift (compile bursts, GC, frequency steps) hits
            # every sweep point alike instead of whichever point happened
            # to be measured last — the flatness/ratio gates compare points
            # against each other, so drift between points is what flakes
            timers = []            # (point name, fn, args)
            for max_seq in sweep:
                ks = jax.random.split(jax.random.fold_in(key, max_seq), 3)
                q = jax.random.normal(ks[0], (b, chunk, hq, hd),
                                      jnp.bfloat16)
                cache = {
                    "k": jax.random.normal(ks[1], (b, max_seq, hkv, hd),
                                           jnp.bfloat16),
                    "v": jax.random.normal(ks[2], (b, max_seq, hkv, hd),
                                           jnp.bfloat16),
                }
                start = jnp.full((b,), window - chunk, jnp.int32)
                args = (q, cache, start)
                timers.append((f"{backend}_win/S{max_seq}", jax.jit(
                    lambda q, c, s: kops.prefill_attention(
                        q, c, s, window=window)), args))
                if backend == "xla":
                    timers.append((f"xla_einsum/S{max_seq}", jax.jit(
                        lambda q, c, s: kops.cached_attention(
                            q, c, s, window=window)), args))
                    timers.append((f"xla_einsum_full/S{max_seq}", jax.jit(
                        lambda q, c, s: kops.cached_attention(
                            q, c, s, window=None)), args))
            t = {}
            for _ in range(2):
                for name, fn, args in timers:
                    t[name] = min(t.get(name, float("inf")),
                                  _timed_min(fn, args, reps))
        finally:
            set_backend(prev)
        for max_seq in sweep:
            t_win = t[f"{backend}_win/S{max_seq}"]
            rows.append((f"prefill_attention/{backend}_win/S{max_seq}",
                         t_win * 1e6,
                         f"chunk={chunk} window={window} slots={b}"))
            if backend == "xla":
                t_ein = t[f"xla_einsum/S{max_seq}"]
                rows.append((f"prefill_attention/xla_einsum/S{max_seq}",
                             t_ein * 1e6,
                             f"window={window} (the replaced hot path) "
                             f"ratio={t_win/t_ein:.2f}x"))
                t_full = t[f"xla_einsum_full/S{max_seq}"]
                rows.append((f"prefill_attention/xla_einsum_full/S{max_seq}",
                             t_full * 1e6,
                             f"window=None ratio={t_full/t_win:.2f}x"))
    return rows


def bench_kernels() -> List[Row]:
    """Kernel micro-bench: bf16 vs W8A8 matmul on the XLA path."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ref
    rows = []
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (256, 1024), jnp.bfloat16)
    w = jax.random.normal(key, (1024, 1024), jnp.bfloat16)
    w_q, w_s = ref.quantize_ref(w, axis=0)

    f_bf16 = jax.jit(lambda a, b: a @ b)
    f_int8 = jax.jit(lambda a, bq, bs: ref.int8_matmul_ref(a, bq, bs))
    for name, f, args in [("matmul_bf16", f_bf16, (x, w)),
                          ("matmul_w8a8", f_int8, (x, w_q, w_s))]:
        jax.block_until_ready(f(*args))
        ts = []
        for _ in range(20):
            t0 = time.perf_counter()
            jax.block_until_ready(f(*args))
            ts.append(time.perf_counter() - t0)
        rows.append((f"kernels/{name}", float(np.median(ts)) * 1e6,
                     "cpu-xla"))
    return rows


def bench_roofline_table() -> List[Row]:
    """SRoofline: one row per dry-run cell (from experiments/dryrun)."""
    rows = []
    if not DRYRUN_DIR.exists():
        return [("roofline/missing", 0.0, "run repro.launch.dryrun first")]
    for f in sorted(DRYRUN_DIR.glob("*__baseline.json")):
        r = json.loads(f.read_text())
        if r.get("status") != "ok":
            continue
        rl = r["roofline"]
        step = rl["step_time_lower_bound_s"]
        rows.append((f"roofline/{r['arch']}/{r['shape']}/{r['mesh']}",
                     step * 1e6,
                     f"dom={rl['dominant'][2:]} useful={rl['useful_flops_ratio']:.2f}"))
    return rows


BENCHES = [
    bench_table1_mobilenetv3,
    bench_table2_resnet18,
    bench_complexity_analysis,
    bench_layerwise_sparsity,
    bench_energy,
    bench_lm_hqp_serving,
    bench_serving,
    bench_speculative,
    bench_paged,
    bench_http,
    bench_chaos,
    bench_decode_attention,
    bench_prefill_attention,
    bench_kernels,
    bench_roofline_table,
]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated bench name suffixes, e.g. "
                         "'serving,kernels'")
    ap.add_argument("--json", default=None,
                    help="write all rows (+ the serving payload) to this "
                         "schema-tagged JSON file (CI perf trajectory)")
    args = ap.parse_args(argv)

    selected = BENCHES
    if args.only:
        want = [w.strip() for w in args.only.split(",") if w.strip()]
        selected = [b for b in BENCHES
                    if any(b.__name__ == f"bench_{w}" or b.__name__ == w
                           for w in want)]
        missing = [w for w in want
                   if not any(b.__name__ in (f"bench_{w}", w)
                              for b in BENCHES)]
        if missing:
            raise SystemExit(f"unknown benches: {missing}; known: "
                             f"{[b.__name__ for b in BENCHES]}")

    all_rows: List[Row] = []
    errors: List[str] = []
    print("name,us_per_call,derived")
    for bench in selected:
        try:
            for name, us, derived in bench():
                all_rows.append((name, us, derived))
                print(f"{name},{us:.2f},{derived}")
        except Exception as e:  # keep the harness running
            errors.append(f"{bench.__name__}:{type(e).__name__}:{e}")
            print(f"{bench.__name__},nan,ERROR:{type(e).__name__}:{e}")

    if args.json:
        payload = {
            "schema": BENCH_SCHEMA,
            "benches": [b.__name__ for b in selected],
            "rows": [{"name": n, "us_per_call": u, "derived": d}
                     for n, u, d in all_rows],
            "errors": errors,
        }
        if _LAST_SERVING:
            payload["serving"] = _LAST_SERVING
        pathlib.Path(args.json).write_text(json.dumps(payload, indent=1))
        print(f"# wrote {args.json} ({len(all_rows)} rows)")
    # CI contract: selected benches must produce rows and no errors
    return 1 if (errors and args.json) else 0


if __name__ == "__main__":
    raise SystemExit(main())
