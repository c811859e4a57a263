"""Chip smoke run: the HQP serving path on one TPU, through its entry points.

    python chip_smoke.py

Runs in ONE process (a TPU belongs to one process at a time) and touches
JAX for nothing before it has checked the device. Phases, each printing its
result, wall seconds and the number of XLA compiles it made:

  a  device and backend: JAX must see a TPU and the kernel backend must
     resolve to compiled ``pallas`` (an inherited REPRO_BACKEND=ref|xla
     fails here)
  b  kernel parity: every serving primitive on ``pallas`` vs ``xla`` at
     qwen3-0.6b widths, within the interpret-mode tests' tolerances
  c  engine, bf16: ``serve --engine --page-size 16 --verify`` at the full
     qwen3-0.6b config (random weights from seed 0); every output
     token-identical to serial decode, no faults, every request finished
     by eos or length
  d  engine, HQP INT8 artifact: the same with ``--hqp`` (Fisher
     sensitivity, conditional pruning, INT8 PTQ and INT8 KV at full width)
  e  HTTP front door: an in-process ``HttpFrontDoor`` on an engine from
     ``build_engine`` answers SSE requests; every stream ends in one
     ``done``, no 5xx, no faults

The last line printed is ``{"ok": true, "device": {...}}``; any failed
check exits non-zero before it. Exits non-zero, printing no result, where
JAX finds no TPU or the repository's ``src/`` is missing.
"""
from __future__ import annotations

import asyncio
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
OUT = ROOT / "experiments" / "chip_smoke"     # git-ignored

# the engine phases: 4 requests of PROMPT_LEN tokens, prefilled in
# PROMPT_LEN / PREFILL_CHUNK chunks, NEW_TOKENS generated each; one prompt
# length and chunk-aligned prompts keep the set of compiled shapes small
N_REQUESTS, PROMPT_LEN, NEW_TOKENS = 4, 256, 32
SERVE_FLAGS = ["--arch", "qwen3-0.6b", "--engine", "--page-size", "16",
               "--prefill-chunk", "128", "--max-seq", "384"]


class CheckFailed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


class CompileCounter:
    """Counts XLA compiles (persistent-cache hits included, reported apart)
    through jax.monitoring; ``take()`` returns the counts since the last
    call."""

    def __init__(self):
        import jax
        self.compiles = self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def take(self):
        out = (self.compiles, self.cache_hits)
        self.compiles = self.cache_hits = 0
        return out


# ------------------------------------------------------------------ phase b
def kernel_parity():
    """Each serving primitive on compiled ``pallas`` vs ``xla`` at
    qwen3-0.6b widths; returns {case: max abs error}."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import configs
    from repro.kernels.backend import get_backend
    from repro.kernels.kv_layout import to_store
    from repro.models.attention import _quant_kv

    cfg = configs.get_config("qwen3-0.6b")
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    b, w, ps, sq = 4, 1024, 16, 64
    n_blk = w // ps
    pallas, xla = get_backend("pallas"), get_backend("xla")
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 64))
    rnd = lambda shape, dt=jnp.bfloat16: jax.random.normal(next(keys), shape,
                                                           dt)

    def kv(lead, length, int8):
        """Unit-normal K/V (what qk-norm'd projections look like); the
        INT8 cache holds them as the engine's own KV write quantizes
        them, per (position, head)."""
        shape = (lead, length, hkv, hd)
        k, v = rnd(shape), rnd(shape)
        if int8:
            (k, k_s), (v, v_s) = _quant_kv(k), _quant_kv(v)
            return k, v, k_s, v_s
        return k, v, None, None

    start_dec = jnp.asarray([0, 100, 517, w - 1], jnp.int32)
    start_pre = jnp.asarray([0, 100, 517, w - sq], jnp.int32)
    # a shuffled page table over an arena with the trash page at 0
    pages = jnp.asarray(1 + np.random.RandomState(0).permutation(b * n_blk)
                        .reshape(b, n_blk), jnp.int32)
    errs = {}

    def compare(name, a, r, rtol, atol):
        a, r = np.asarray(a, np.float32), np.asarray(r, np.float32)
        check(a.shape == r.shape and np.all(np.isfinite(a)),
              f"{name}: shape {a.shape} vs {r.shape} or non-finite output")
        errs[name] = float(np.max(np.abs(a - r)))
        check(np.allclose(a, r, rtol=rtol, atol=atol),
              f"{name}: max abs error {errs[name]} beyond rtol={rtol} "
              f"atol={atol}")

    for int8 in (False, True):
        tag = "int8" if int8 else "bf16"
        k, v, k_s, v_s = kv(b, w, int8)
        q1, qs = rnd((b, hq, hd)), rnd((b, sq, hq, hd))
        atol = 1e-1 if int8 else 3e-2
        compare(f"decode/{tag}",
                pallas.decode_attention(q1, k, v, k_s, v_s, start_dec),
                xla.decode_attention(q1, k, v, k_s, v_s, start_dec),
                3e-2, atol)
        compare(f"prefill/{tag}",
                pallas.prefill_attention(qs, k, v, k_s, v_s, start_pre),
                xla.prefill_attention(qs, k, v, k_s, v_s, start_pre),
                3e-2, 1.5e-1 if int8 else 3e-2)
        # the paged arena: uint16 words for bf16 (as the engine stores it)
        ak, av, aks, avs = kv(1 + b * n_blk, ps, int8)
        if not int8:
            ak, av = to_store(ak, jnp.uint16), to_store(av, jnp.uint16)
        tag = "int8" if int8 else "uint16"
        compare(f"paged_decode/{tag}",
                pallas.decode_attention_paged(q1, ak, av, aks, avs,
                                              start_dec, pages),
                xla.decode_attention_paged(q1, ak, av, aks, avs, start_dec,
                                           pages),
                3e-2, atol)
        compare(f"paged_prefill/{tag}",
                pallas.prefill_attention_paged(qs, ak, av, aks, avs,
                                               start_pre, pages),
                xla.prefill_attention_paged(qs, ak, av, aks, avs, start_pre,
                                            pages),
                3e-2, 1.5e-1 if int8 else 3e-2)

    d, f = cfg.d_model, cfg.d_ff
    w_q, w_s = xla.quantize_rowwise(rnd((f, d), jnp.float32))
    w_q, w_s = w_q.T, w_s                     # (d, f) int8, per-out-channel
    for m in (4, 64):
        x_q, x_s = xla.quantize_rowwise(rnd((m, d), jnp.float32) * 3)
        compare(f"int8_matmul/M{m}", pallas.int8_matmul(x_q, w_q, x_s, w_s),
                xla.int8_matmul(x_q, w_q, x_s, w_s), 2e-2, 2e-2)
    for m in (4, 512):
        x = rnd((m, d), jnp.float32) * 3
        (qp, sp), (qr, sr) = pallas.quantize_rowwise(x), xla.quantize_rowwise(x)
        compare(f"quantize_rowwise/M{m}/scale", sp, sr, 1e-6, 0.0)
        compare(f"quantize_rowwise/M{m}/q", qp, qr, 0.0, 0.0)
    return errs


# ------------------------------------------------------------- phases c, d
def write_trace() -> pathlib.Path:
    """Staggered arrivals, one prompt length; prompts synthesized from the
    trace loader's seed."""
    OUT.mkdir(parents=True, exist_ok=True)
    path = OUT / "trace.jsonl"
    path.write_text("".join(
        json.dumps({"arrival_s": 0.05 * i, "prompt_len": PROMPT_LEN,
                    "max_new_tokens": NEW_TOKENS}) + "\n"
        for i in range(N_REQUESTS)))
    return path


def serve_engine(extra):
    """``serve --engine --verify`` in process; serve itself exits non-zero
    on a verify mismatch or an errored request — this re-checks the stats
    it returns."""
    from repro.launch import serve
    try:
        stats = serve.main(SERVE_FLAGS + ["--verify", "--trace",
                                          str(write_trace())] + extra)
    except SystemExit as e:
        if e.__cause__ is not None:
            import traceback
            traceback.print_exception(e.__cause__)
        raise CheckFailed(f"serve exited: {e}") from e
    check(stats["n_requests"] == N_REQUESTS,
          f"{stats['n_requests']} of {N_REQUESTS} requests finished")
    check(stats["faults"] == 0, f"engine faults: {stats['faults']}")
    check(set(stats["finish_reasons"]) <= {"eos", "length"},
          f"finish reasons {stats['finish_reasons']}")
    return (f"{stats['out_tokens']} tokens, finish {stats['finish_reasons']}, "
            f"token-identical to serial decode")


# ------------------------------------------------------------------ phase e
async def sse_generate(port: int, body: dict) -> dict:
    """One streaming client: POST, read the SSE stream to its end; returns
    the status and the names of the events received."""
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    data = json.dumps(body).encode()
    writer.write(b"POST /v1/generate HTTP/1.1\r\nHost: smoke\r\n"
                 b"Content-Length: %d\r\n\r\n" % len(data) + data)
    await writer.drain()
    head = await reader.readuntil(b"\r\n\r\n")
    raw = await reader.read()
    writer.close()
    events = [line[len(b"event: "):].decode()
              for line in raw.split(b"\n") if line.startswith(b"event: ")]
    return {"status": int(head.split(b" ")[1]), "events": events}


def http_front_door():
    import jax
    from repro import configs
    from repro.launch import serve
    from repro.models import lm
    from repro.serving.service import HttpFrontDoor, Service
    from repro.sharding.ctx import default_ctx

    args = serve.build_parser().parse_args(SERVE_FLAGS)
    cfg = configs.get_config(args.arch)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    eng = serve.build_engine(params, cfg, default_ctx(), args)
    svc = Service(eng)
    door = HttpFrontDoor(svc, host="127.0.0.1", port=0)

    async def go():
        await door.start()
        try:
            return await asyncio.gather(*[
                sse_generate(door.port, {"prompt_len": PROMPT_LEN,
                                         "max_new_tokens": NEW_TOKENS})
                for _ in range(N_REQUESTS)])
        finally:
            await door.stop(drain=True)

    recs = asyncio.run(go())
    check(all(r["status"] == 200 for r in recs),
          f"statuses {[r['status'] for r in recs]}")
    check(all(r["events"].count("done") == 1 and r["events"][-1] == "done"
              and r["events"].count("token") == NEW_TOKENS for r in recs),
          f"streams {[r['events'][-3:] for r in recs]}")
    check(svc.stats["faults"] == 0 and eng.stats["faults"] == 0,
          f"faults: service {svc.stats['faults']}, engine "
          f"{eng.stats['faults']}")
    check(svc.stats["completed"] == N_REQUESTS,
          f"completed {svc.stats['completed']} of {N_REQUESTS}")
    return f"{N_REQUESTS} SSE streams, each {NEW_TOKENS} tokens and one done"


# -------------------------------------------------------------------- main
def main() -> int:
    t0 = time.monotonic()
    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu":
        print(f"[a] FAIL: JAX found platform {dev.platform!r} "
              f"({dev.device_kind}), not a TPU", file=sys.stderr)
        return 1
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"[a] FAIL: no repro package under {src}", file=sys.stderr)
        return 1
    sys.path.insert(0, str(src))
    from repro.kernels.backend import get_backend
    from repro.launch.compile_cache import use_compile_cache

    print(f"[smoke] compile cache: {use_compile_cache()}")
    counter = CompileCounter()
    name = get_backend().name
    if name != "pallas":
        print(f"[a] FAIL: kernel backend resolves to {name!r}, not compiled "
              f"'pallas' (is REPRO_BACKEND set?)", file=sys.stderr)
        return 1
    print(f"[a] ok {time.monotonic() - t0:.1f}s: {device['kind']} x"
          f"{device['count']}, backend {name}")

    phases = [
        ("b", "kernel parity", lambda: "max abs error " + ", ".join(
            f"{k}={v:.3g}" for k, v in kernel_parity().items())),
        ("c", "engine bf16", lambda: serve_engine([])),
        ("d", "engine HQP INT8", lambda: serve_engine(["--hqp"])),
        ("e", "http front door", http_front_door),
    ]
    for tag, title, run in phases:
        t = time.monotonic()
        counter.take()
        try:
            detail = run()
        except CheckFailed as e:
            print(f"[{tag}] FAIL {title} {time.monotonic() - t:.1f}s: {e}",
                  file=sys.stderr)
            return 1
        compiles, hits = counter.take()
        print(f"[{tag}] ok {title} {time.monotonic() - t:.1f}s, "
              f"{compiles} compiles ({hits} persistent-cache hits): {detail}",
              flush=True)
    print(f"[smoke] total {time.monotonic() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
