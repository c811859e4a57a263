"""Serving launcher: HQP artifacts through the batched or continuous-batching path.

Deliverable (b) inference driver: acquires a model (fresh init, full HQP
pipeline, or a saved artifact — loading NEVER re-runs sensitivity /
calibration), prints the artifact manifest, then serves synthetic requests.

Two serving paths:

  default      one batch, lockstep prefill + decode (the PR-1 smoke loop)
  --engine     continuous batching (``repro.serving.Engine``): slot-based
               admission/eviction, chunked prefill interleaved with batched
               decode, per-request latency stats; replays a request trace
               (``--trace``, JSONL) or a synthetic staggered-arrival load.
               With ``--verify`` (default under ``--smoke``) every engine
               output is checked token-identical against serial decode.

``--temperature/--top-k/--seed`` drive seeded sampling on every decode
surface (default greedy). ``--spec-k N`` (engine mode, with ``--hqp`` or
``--load-artifact``) turns on self-speculative serving: the HQP artifact
drafts N tokens per cycle, the bf16 parent verifies — greedy output stays
bit-identical to serial bf16 decode (``--verify`` checks exactly that).

  python -m repro.launch.serve --arch qwen3-0.6b --smoke --hqp --tokens 32
  python -m repro.launch.serve --arch qwen3-0.6b --smoke --engine
  python -m repro.launch.serve --arch qwen3-0.6b --smoke --engine --hqp \\
      --spec-k 4
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import lm
from repro.sharding.ctx import make_ctx
from repro.train.train_step import make_eval_step, make_serve_step


def _calib_batch(cfg, batch: int, seq: int, seed: int = 17) -> dict:
    rng = np.random.RandomState(seed)
    b = {"tokens": jnp.asarray(
        rng.randint(0, cfg.vocab_size, (batch, seq)), jnp.int32)}
    if cfg.frontend.kind != "none":
        b["embeds"] = jnp.zeros((batch, cfg.frontend.n_embeds, cfg.d_model),
                                jnp.bfloat16)
    return b


def build_artifact(params, cfg, ctx, prune_steps: int, log=print):
    """HQP artifact for serving: one-batch Fisher pass + next-token-accuracy
    eval drive the conditional prune; PTQ is the jitted on-device path."""
    from repro.core.pipeline import HQPConfig
    from repro.core.sensitivity import fisher_diag
    from repro.compress import compress

    batch = _calib_batch(cfg, batch=2, seq=32)
    grad = jax.jit(jax.grad(
        lambda p, b: lm.loss_fn(p, cfg, b, ctx, with_aux=False)[0]))
    sq, _ = fisher_diag(grad, params, [batch])
    eval_step = jax.jit(make_eval_step(cfg, ctx))
    eval_fn = lambda p: float(eval_step(p, batch))
    hqp = HQPConfig(weight_granularity="channel", step_frac=0.05,
                    max_steps=prune_steps)
    return compress(params, cfg, sq_grads=sq, eval_fn=eval_fn, hqp=hqp,
                    log=log)


def acquire_params(args, cfg, ctx, log=print):
    """Resolve the model to serve. Exactly one of three paths runs:

    load-artifact  deserialize; NO gradients, NO Fisher pass, NO eval — a
                   saved artifact already paid for its calibration
    --hqp          init + full pipeline (optionally --save-artifact)
    plain          fresh bf16 init

    Returns ``(params, manifest, parent)``: ``manifest`` is the HQP
    manifest when ``params`` is an artifact (else None); ``parent`` is the
    full-precision pytree the artifact was compressed from when it exists
    in-process (the --hqp path) — the speculative verifier.
    """
    if args.load_artifact:
        from repro.launch.checkpoint import load_artifact
        art = load_artifact(args.load_artifact)
        if art.manifest.arch != cfg.name:
            raise SystemExit(
                f"artifact was built for {art.manifest.arch!r}, requested "
                f"config is {cfg.name!r} — pass the matching --arch/--smoke")
        log(art.manifest.summary())
        return art.params, art.manifest, None
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    if args.hqp:
        art = build_artifact(params, cfg, ctx, args.prune_steps, log=log)
        log(art.manifest.summary())
        if args.save_artifact:
            from repro.launch.checkpoint import save_artifact
            log(f"[serve] artifact saved to "
                f"{save_artifact(args.save_artifact, art)}")
        return art.params, art.manifest, params
    return params, None, None


# ------------------------------------------------------------------ engine
def _attach_tracer(eng, trace_dir):
    """Hang a span recorder off the engine when ``--trace-dir`` asks for
    one. The recorder is a passive sink — the engine stamps every event
    with its own injectable clock, so attaching it costs nothing until
    events actually flow."""
    if not trace_dir:
        return None
    from repro import telemetry
    tracer = telemetry.SpanRecorder()
    eng.tracer = tracer
    return tracer


def _write_tracer(tracer, trace_dir, log):
    if tracer is None:
        return
    from repro import telemetry
    trace_path, jsonl_path = telemetry.write_trace(trace_dir, tracer)
    log(f"[trace] wrote {trace_path} (Perfetto/chrome://tracing) and "
        f"{jsonl_path}")


def _profile(profile_dir):
    """``--profile-dir``: a device profile around the engine run. A
    profiler that cannot start or stop fails the run: a serve asked for a
    profile must not finish without one."""
    if not profile_dir:
        return contextlib.nullcontext()
    return jax.profiler.trace(profile_dir)


def _fail_on_errors(eng, results, what: str) -> None:
    """Request-scoped fault isolation finishes a faulted request with
    ``finish_reason="error"`` and keeps serving; a launcher run that
    produced one must still exit non-zero, with the engine's last fault
    as the cause."""
    bad = sorted(i for i, r in results.items() if r.finish_reason == "error")
    if bad or eng.stats["faults"]:
        raise SystemExit(
            f"{what}: requests {bad} finished with an error "
            f"({eng.stats['faults']} engine faults; last: "
            f"{eng.last_fault!r})") from eng.last_fault


def load_trace(path: str, cfg, seed: int = 0):
    """JSONL request trace: one object per line with ``arrival_s`` (float,
    offset from replay start) and either ``prompt`` (token ids) or
    ``prompt_len`` (synthesized from ``seed``); optional ``max_new_tokens``
    (default 16) and ``eos_id``."""
    from repro.serving import Request
    rng = np.random.RandomState(seed)
    reqs, arrivals = [], []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            d = json.loads(line)
            if "prompt" in d:
                prompt = d["prompt"]
                if not prompt:
                    raise ValueError(f"trace line has an empty prompt: {d}")
            elif "prompt_len" in d:
                prompt = rng.randint(
                    0, cfg.vocab_size, int(d["prompt_len"])).tolist()
            else:
                raise ValueError(
                    f"trace line needs 'prompt' or 'prompt_len': {d}")
            reqs.append(Request(prompt=prompt,
                                max_new_tokens=int(d.get("max_new_tokens", 16)),
                                eos_id=d.get("eos_id")))
            arrivals.append(float(d.get("arrival_s", 0.0)))
    return reqs, arrivals


def synth_requests(cfg, n: int, prompt_len: int, max_new_tokens: int,
                   gap_s: float = 0.02, seed: int = 0):
    """Staggered synthetic load: varying prompt lengths so chunked prefill
    genuinely interleaves with decode of earlier requests."""
    from repro.serving import Request
    rng = np.random.RandomState(seed)
    lens = [max(4, prompt_len + (i * 7) % 11 - 5) for i in range(n)]
    reqs = [Request(prompt=rng.randint(0, cfg.vocab_size, L).tolist(),
                    max_new_tokens=max_new_tokens) for L in lens]
    return reqs, [i * gap_s for i in range(n)]


def build_engine(params, cfg, ctx, args, sampling=None, draft=None):
    """One Engine from the serve flags — shared by trace replay
    (``run_engine``) and the HTTP front door (``serve_http``), so both
    paths serve the exact same configuration."""
    from repro.serving import Engine, SchedulerConfig
    spec_kw = {}
    if draft is not None:
        draft_params, draft_ctx, manifest = draft
        spec_kw = dict(draft_params=draft_params, draft_ctx=draft_ctx,
                       spec_k=args.spec_k, draft_manifest=manifest)
    return Engine(params, cfg, ctx=ctx, n_slots=args.engine_slots,
                  max_seq=args.max_seq,
                  sched=SchedulerConfig(prefill_chunk=args.prefill_chunk,
                                        decode_steps=args.decode_steps),
                  sampling=sampling, page_size=args.page_size or None,
                  total_pages=getattr(args, "total_pages", 0) or None,
                  prefix_cache=not args.no_prefix_cache, **spec_kw)


def serve_http(params, cfg, ctx, args, log=print, sampling=None, draft=None):
    """``serve --http``: the engine behind the asyncio SSE front door.
    Blocks until SIGTERM/SIGINT, then drains in-flight slots (DESIGN §13).
    A warmup request pays the jit-compile cost before the listener opens so
    the first client's TTFT measures serving, not tracing."""
    from repro.serving import Request
    from repro.serving.service import Service, ServiceConfig, run_http
    eng = build_engine(params, cfg, ctx, args, sampling=sampling, draft=draft)
    t0 = time.monotonic()
    warm = eng.run([Request(prompt=[3, 1, 4, 1, 5, 9], max_new_tokens=2)])
    _fail_on_errors(eng, warm, "[http] warmup")
    for k in eng.stats:
        eng.stats[k] = 0
    log(f"[http] warmup compile: {time.monotonic() - t0:.1f}s")
    admission = None
    if not args.no_feasibility:
        from repro.serving import AdmissionController
        admission = AdmissionController()
    svc = Service(eng, ServiceConfig(queue_depth=args.queue_depth,
                                     default_deadline_s=args.deadline_s),
                  admission=admission)
    # attach AFTER the warmup request so the trace starts at the first
    # client-visible submit
    tracer = _attach_tracer(eng, args.trace_dir)
    with _profile(args.profile_dir):
        run_http(svc, host=args.host, port=args.port, log=log,
                 watchdog_s=args.watchdog_s or None)
    _write_tracer(tracer, args.trace_dir, log)
    return svc


def run_engine(params, cfg, ctx, args, log=print, sampling=None, draft=None):
    """``draft`` = (draft_params, draft_ctx, manifest) switches the engine
    into speculative mode: ``params`` is then the bf16 VERIFIER and the
    drafter is the HQP artifact. ``--verify`` still compares against serial
    decode of ``params`` — in speculative greedy mode that is exactly the
    bit-identity guarantee (the artifact only ever proposes)."""
    from repro.serving import serial_decode, summarize_results
    if args.trace:
        reqs, arrivals = load_trace(args.trace, cfg)
        log(f"[engine] replaying trace {args.trace}: {len(reqs)} requests")
    else:
        n = max(3, args.batch)
        reqs, arrivals = synth_requests(cfg, n, args.prompt_len, args.tokens)
        log(f"[engine] synthetic load: {n} staggered requests")
    if not reqs:
        raise SystemExit("[engine] trace contains no requests")
    need = max(len(r.prompt) + r.max_new_tokens for r in reqs)
    if need > args.max_seq:
        raise SystemExit(f"trace needs max-seq >= {need}, got {args.max_seq}")

    eng = build_engine(params, cfg, ctx, args, sampling=sampling, draft=draft)
    tracer = _attach_tracer(eng, args.trace_dir)
    with _profile(args.profile_dir):
        t0 = time.monotonic()
        results = eng.run(reqs, arrivals_s=arrivals)
        wall = time.monotonic() - t0
    _write_tracer(tracer, args.trace_dir, log)

    stats = {
        **summarize_results(results, wall),
        "finish_reasons": dict(collections.Counter(
            r.finish_reason for r in results.values())),
        "n_slots": args.engine_slots,
        "prefill_chunk": args.prefill_chunk,
        **eng.stats,
    }
    accept = (eng.stats["accepted_tokens"] /
              max(eng.stats["drafted_tokens"], 1))
    stats["acceptance_rate"] = accept
    log(f"[engine] {stats['n_requests']} requests in {wall*1000:.0f}ms: "
        f"{stats['tokens_per_s']:.1f} tok/s, "
        f"latency p50/p95 {stats['latency_p50_ms']:.0f}/"
        f"{stats['latency_p95_ms']:.0f}ms, "
        f"ttft p50/p95 {stats['ttft_p50_ms']:.0f}/"
        f"{stats['ttft_p95_ms']:.0f}ms "
        f"(ticks: {eng.stats['prefill_ticks']}p/{eng.stats['decode_ticks']}d, "
        f"{eng.stats['device_steps']} device decode steps / "
        f"{eng.stats['host_syncs']} host syncs"
        + (f", spec acceptance {accept:.2f}" if draft is not None else "")
        + (f", {eng.stats['prefix_hits']} prefix hits / "
           f"{eng.stats['pages_peak']} pages peak" if args.page_size else "")
        + ")")
    _fail_on_errors(eng, results, "[engine]")

    verify = args.verify if args.verify is not None else args.smoke
    if verify and draft is not None and sampling is not None \
            and not sampling.is_greedy:
        log("[engine] verify skipped: speculative sampling matches the "
            "verifier's DISTRIBUTION, not its token sequence (greedy "
            "speculative mode is token-identical and verifiable)")
        verify = False
    if verify:
        bad = {}
        for i, res in sorted(results.items()):
            req = reqs[i]
            ref = serial_decode(params, cfg, req.prompt, req.max_new_tokens,
                                ctx=ctx, max_seq=args.max_seq,
                                eos_id=req.eos_id, sampling=sampling,
                                decode_rows=args.engine_slots)
            if res.tokens != ref:
                bad[i] = next((t for t, (a, b) in enumerate(
                    zip(res.tokens, ref)) if a != b),
                    min(len(res.tokens), len(ref)))
        if bad:
            raise SystemExit(f"[engine] VERIFY FAILED: requests {sorted(bad)} "
                             f"differ from serial single-request decode "
                             f"(first differing token index by request: "
                             f"{bad})")
        log(f"[engine] verify: all {len(results)} outputs token-identical "
            f"to serial decode")
    return results, stats


# -------------------------------------------------------------------- main
def build_parser() -> argparse.ArgumentParser:
    """The serve command line (``main`` parses it; ``chip_smoke.py`` reuses
    it so its in-process front door serves the same flags)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--hqp", action="store_true",
                    help="full HQP artifact: prune -> INT8 weights + INT8 KV")
    ap.add_argument("--prune-steps", type=int, default=3,
                    help="conditional-prune δ-steps for the serving artifact")
    ap.add_argument("--save-artifact", default=None,
                    help="directory to persist the HQP artifact (atomic)")
    ap.add_argument("--load-artifact", default=None,
                    help="serve a previously saved HQP artifact (skips all "
                         "sensitivity/calibration work)")
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--engine", action="store_true",
                    help="continuous-batching engine instead of the "
                         "single-batch lockstep loop")
    ap.add_argument("--engine-slots", type=int, default=4)
    ap.add_argument("--prefill-chunk", type=int, default=16)
    ap.add_argument("--decode-steps", type=int, default=4,
                    help="batched decode steps per device dispatch (the "
                         "jitted lax.scan length; 1 = sync every token)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative draft length: the HQP artifact drafts "
                         "K tokens per cycle, the bf16 parent verifies "
                         "(engine mode, requires --hqp or --load-artifact; "
                         "0 = off)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy, the default)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k sampling cutoff (0 = full vocabulary)")
    ap.add_argument("--seed", type=int, default=0,
                    help="sampling seed; same seed => same tokens, engine "
                         "and serial alike")
    ap.add_argument("--page-size", type=int, default=0,
                    help="paged KV cache: arena page size in tokens (engine "
                         "mode; 0 = contiguous per-slot pool). Outputs are "
                         "token-identical at every page size")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable hash-keyed shared-prefix page reuse "
                         "(paged mode only)")
    ap.add_argument("--total-pages", type=int, default=0,
                    help="paged KV arena size in pages (0 = full "
                         "provisioning, 1 + slots*ceil(max_seq/page_size)); "
                         "undersizing forces arena-exhaustion behavior — "
                         "chaos testing / memory-capped deployments")
    ap.add_argument("--trace", default=None,
                    help="JSONL request trace to replay (engine mode)")
    ap.add_argument("--trace-dir", default=None,
                    help="write per-request span traces here after the run: "
                         "trace.json (Chrome trace-event JSON, loadable in "
                         "Perfetto or chrome://tracing) plus spans.jsonl "
                         "(engine mode, trace replay or --http)")
    ap.add_argument("--profile-dir", default=None,
                    help="wrap the engine run in jax.profiler.trace and "
                         "write the device profile here (engine mode; a "
                         "profiler that fails to start or stop fails the "
                         "run)")
    ap.add_argument("--http", action="store_true",
                    help="serve over HTTP with SSE token streaming instead "
                         "of replaying a trace (implies --engine; blocks "
                         "until SIGTERM, then drains in-flight requests)")
    ap.add_argument("--host", default="127.0.0.1",
                    help="HTTP bind address (--http)")
    ap.add_argument("--port", type=int, default=8080,
                    help="HTTP bind port; 0 picks a free port (--http)")
    ap.add_argument("--queue-depth", type=int, default=16,
                    help="admission queue bound beyond the slots: more than "
                         "slots+depth requests in flight => shed with 429 "
                         "(--http)")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="default per-request deadline in seconds; expired "
                         "requests are evicted mid-flight and stream "
                         "finish_reason=deadline (--http; per-request "
                         "'deadline_s' in the POST body overrides)")
    ap.add_argument("--no-feasibility", action="store_true",
                    help="disable deadline-feasibility admission (the EWMA "
                         "throughput predictor that sheds deadlined "
                         "requests it cannot serve in time, DESIGN.md §14); "
                         "the static slots+queue-depth cap always applies")
    ap.add_argument("--watchdog-s", type=float, default=300.0,
                    help="pump watchdog: if the engine thread makes no "
                         "progress for this long the server exits with "
                         "status 2 instead of hanging (0 disables; --http)")
    ap.add_argument("--verify", action="store_true", default=None,
                    help="check engine outputs == serial decode "
                         "(default: on under --smoke)")
    return ap


def main(argv=None):
    ap = build_parser()
    args = ap.parse_args(argv)

    if args.http:
        args.engine = True           # the front door is an engine transport
        if args.trace:
            ap.error("--http serves live requests; --trace replays a file — "
                     "pick one")
    if args.save_artifact and not args.hqp:
        ap.error("--save-artifact requires --hqp (nothing to save otherwise)")
    if args.save_artifact and args.load_artifact:
        ap.error("--save-artifact with --load-artifact would just copy the "
                 "artifact; use the filesystem for that")
    if args.page_size and not args.engine:
        ap.error("--page-size needs --engine (the lockstep loop has no "
                 "slot pool to page)")
    if (args.trace_dir or args.profile_dir) and not args.engine:
        ap.error("--trace-dir/--profile-dir need --engine (spans and phase "
                 "attribution are engine-step concepts)")
    use_hqp = args.hqp or args.load_artifact is not None
    if args.spec_k:
        if not args.engine:
            ap.error("--spec-k needs --engine (speculation is an engine "
                     "decode mode)")
        if not use_hqp:
            ap.error("--spec-k needs a drafter: pass --hqp (build one) or "
                     "--load-artifact")

    use_compile_cache()
    cfg = (configs.get_smoke_config(args.arch) if args.smoke
           else configs.get_config(args.arch))
    mesh = make_host_mesh()
    ctx = make_ctx(mesh, batch_sharded=False, quantized_kv=use_hqp)

    params, manifest, parent = acquire_params(args, cfg, ctx)
    from repro.serving import SamplingConfig
    sampling = SamplingConfig(temperature=args.temperature,
                              top_k=args.top_k, seed=args.seed)

    if args.engine:
        draft = None
        if args.spec_k:
            if parent is None:
                # --load-artifact path: the artifact's parent weights are
                # not in the checkpoint; re-init the deterministic seed-0
                # parent (manifest arch-hash still guards arch mismatch).
                # Loud on purpose: if the artifact came from ANY other
                # weights (different seed, trained checkpoint), this
                # verifier is an unrelated model — output stays
                # verifier-faithful but acceptance collapses.
                print("[serve] WARNING: --spec-k with --load-artifact "
                      "re-initializes the seed-0 bf16 parent as the "
                      "verifier; if the artifact was built from other "
                      "weights, expect near-zero acceptance (pass --hqp "
                      "to build drafter and verifier from the same "
                      "params)")
                parent = lm.init_params(jax.random.PRNGKey(0), cfg)
            draft_ctx = ctx                  # quantized_kv=True: INT8 KV
            ctx = dataclasses.replace(ctx, quantized_kv=False)  # verifier
            draft = (params, draft_ctx, manifest)
            params = parent
        with mesh:
            if args.http:
                svc = serve_http(params, cfg, ctx, args, sampling=sampling,
                                 draft=draft)
                return svc.stats
            _, stats = run_engine(params, cfg, ctx, args, sampling=sampling,
                                  draft=draft)
        return stats

    serve_step = jax.jit(make_serve_step(cfg, ctx), donate_argnums=(1,))

    with mesh:
        state = lm.init_decode_state(cfg, args.batch, args.max_seq, ctx,
                                     params=params if use_hqp else None)
        rng = np.random.RandomState(0)
        prompts = jnp.asarray(rng.randint(
            0, cfg.vocab_size, (args.batch, args.prompt_len)), jnp.int32)
        t0 = time.time()
        if cfg.frontend.kind != "none":
            embeds = jnp.zeros((args.batch, cfg.frontend.n_embeds,
                                cfg.d_model), jnp.bfloat16)
            logits, state = lm.decode_step(params, cfg, state, prompts, ctx,
                                           embeds)
        else:
            logits, state = serve_step(params, state, prompts)
        jax.block_until_ready(logits)
        t_prefill = time.time() - t0

        # sampling on the lockstep path shares the engine's key rule (seed x
        # absolute position); greedy stays on the original argmax
        from repro.serving import sampling as smp
        base = smp.base_key(sampling)
        pick = jax.jit(lambda lg, pos: smp.sample_batch(
            lg[:, -1], sampling, base,
            jnp.full((lg.shape[0],), pos, jnp.int32))[:, None])
        pos = args.prompt_len
        tok = pick(logits, pos)
        outputs = [tok]
        t0 = time.time()
        for _ in range(args.tokens - 1):
            logits, state = serve_step(params, state, tok)
            pos += 1
            tok = pick(logits, pos)
            outputs.append(tok)
        jax.block_until_ready(tok)
        t_decode = time.time() - t0

    out = jnp.concatenate(outputs, axis=1)
    tps = args.batch * (args.tokens - 1) / max(t_decode, 1e-9)
    print(f"[serve] prefill {args.batch}x{args.prompt_len} in "
          f"{t_prefill*1000:.1f}ms; decode {args.tokens-1} steps: "
          f"{tps:.1f} tok/s")
    print(f"[serve] sample continuation (req 0): {np.asarray(out[0])[:16]}")
    return out


if __name__ == "__main__":
    main()
