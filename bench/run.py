"""Run one cell of the benchmark once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name: the cell in ``BENCHMARK.json``; its
configuration in the file the benchmark names (``bench/configs``), and the
plain reference ``bench/reference/<reference>.py`` that the file names,
which also gives the sizes the program's configuration must hold and the
served model's work (the interface is in ``bench/reference/__init__.py``);
its traffic mix in ``bench/traffic/<traffic>.json``, generated by the
module ``bench/traffic/<kind>.py`` that the mix names; each per-layer
metric by a reader ``bench/metrics/<metric>.py``.

One run: set-up (runtime start, weights made on the device from the seed,
the HQP artifact where the configuration asks for it, the engine built the
way ``serve`` builds it, every shape the run's own traffic needs warmed up
through the engine), then the window: the traffic, computed from the seed,
is sent to an in-process ``HttpFrontDoor`` (``POST /v1/generate``, SSE) by a
load generator in a child process that never imports JAX (``loadgen.py``).
Set-up ends when the first request is due. Latencies count from due times.
After the window, load runs on until every request due in it has finished;
then the served tokens are compared with the plain reference.

With ``--trace 0`` the result holds the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, read from the engine's spans, the
benchmark's own host annotations and a profiler trace of a few seconds of
the window. The last line of standard output is one JSON object; the last
lines of standard error are the numbers compared, each beside its limit.

Exits 2, printing no result, where JAX finds no TPU, fewer chips than the
cell asks for, or no program beside the benchmark.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import contextlib  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import types  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
OUT = BENCH / ".out"                     # git-ignored
CACHE = BENCH / ".jax_cache"             # git-ignored, fixed path
LEAD_S = 0.3          # first request due this long after set-up ends
DRAIN_S = 60.0        # how long past the window answers are waited for
TRACE_S = 3.0         # profiler stretch of a --trace 1 run

if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import correctness, devtrace, stats, weights, work  # noqa: E402


class NoChip(Exception):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str) -> dict:
    """The cell's entry, configuration file, traffic mix and metric lists,
    by name from ``BENCHMARK.json``."""
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in bm["configs"]}[cell["config"]]
    mine = lambda ms: [m for m in ms
                       if name in m.get("workloads", [name])]
    return {"name": name, "chips": cell["chips"],
            "config": json.loads((ROOT / entry["file"]).read_text()),
            "mix": json.loads((BENCH / "traffic" /
                               f"{cell['traffic']}.json").read_text()),
            "end_to_end": mine(bm["end_to_end"]),
            "per_layer": mine(bm["per_layer"])}


class Setup:
    """Seconds of each set-up step, for the set-up line of the run."""

    def __init__(self):
        self.steps = {}

    @contextlib.contextmanager
    def step(self, name):
        t = time.monotonic()
        yield
        self.steps[name] = self.steps.get(name, 0.0) + time.monotonic() - t


# ------------------------------------------------------------ the system
def program_config(cfg_file: dict, ref):
    """The program's configuration, checked against the sizes the
    reference's ``program_sizes`` reads from the file."""
    from repro import configs
    get = (configs.get_smoke_config if cfg_file.get("preset") == "smoke"
           else configs.get_config)
    pc = get(cfg_file["arch"])
    want = ref.program_sizes(cfg_file["model"])
    if not want:
        raise SystemExit(f"the reference {cfg_file['reference']!r} gives no "
                         "size to check the program's configuration by")
    have = {k: functools.reduce(getattr, k.split("."), pc) for k in want}
    if have != want:
        raise SystemExit(f"program config {cfg_file['arch']} is not the "
                         f"file's: {have} != {want}")
    return pc


def build_params(cfg_file, pc, seed, setup):
    import jax
    from repro.models import lm
    with setup.step("weights"):
        layout = jax.eval_shape(lambda k: lm.init_params(k, pc),
                                jax.random.PRNGKey(0))
        params = weights.make(layout, seed, cfg_file["model"]
                              ["initializer_range"])
        jax.block_until_ready(params)
    return params


def compress(cfg_file, pc, ctx, params, ref, dims, setup):
    """The HQP artifact: the program's ``compress`` (conditional prune,
    compaction, INT8 PTQ), with the settings of ``serve.build_artifact``
    as the configuration file states them, on a diagonal Fisher that the
    benchmark computes with the reference, so the reference prunes the
    same units. Returns (artifact, reference keep masks, units the
    reference drops); ``check`` compares that count with the program's."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.compress import compress as program_compress
    from repro.core.pipeline import HQPConfig
    from repro.train.train_step import make_eval_step
    h = cfg_file["hqp"]
    c = h["calibration"]
    calib = np.random.RandomState(c["seed"]).randint(
        0, dims.vocab, (c["batch"], c["seq"]))
    with setup.step("fisher"):
        sq = ref.fisher_diag(params, dims, jnp.asarray(calib))
        jax.block_until_ready(sq)
    with setup.step("compress"):
        eval_step = jax.jit(make_eval_step(pc, ctx))
        batch = {"tokens": jnp.asarray(calib, jnp.int32)}
        art = program_compress(
            params, pc, sq_grads=sq,
            eval_fn=lambda p: float(eval_step(p, batch)),
            hqp=HQPConfig(weight_granularity=h["granularity"],
                          step_frac=h["step_frac"], delta_ax=h["delta_ax"],
                          max_steps=h["prune_steps"]),
            log=lambda s: None)
        jax.block_until_ready(art.params)
    with setup.step("reference_prune"):
        keep, n_drop = ref.prune_units(params, dims, sq, jnp.asarray(calib),
                                       h["step_frac"], h["prune_steps"],
                                       h["delta_ax"])
    del sq
    return art, keep, n_drop


def build_engine(cfg_file, mix, pc, ctx, params):
    from repro.launch import serve
    e = mix["engine"]
    args = serve.build_parser().parse_args(
        cfg_file["engine"] + ["--engine-slots", str(e["n_slots"]),
                              "--max-seq", str(e["max_seq"]),
                              "--queue-depth", str(e["queue_depth"])])
    return serve.build_engine(params, pc, ctx, args), args


def warm(eng, stages, setup, name):
    """Run each stage's requests through the engine to completion."""
    from repro.serving import Request
    with setup.step(name):
        for stage in stages:
            for prompt, n_new in stage:
                eng.submit(Request(prompt=prompt, max_new_tokens=n_new))
            while eng.has_work:
                eng.step()
        if eng.stats["faults"]:
            raise RuntimeError(f"set-up faulted: {eng.last_fault!r}")


async def _cancel_others():
    """Cancel every other task of the running loop (open streams)."""
    me = asyncio.current_task()
    for t in asyncio.all_tasks():
        if t is not me:
            t.cancel()
    await asyncio.sleep(0.1)


class DoorThread:
    """The front door's asyncio loop on a thread of its own, so the main
    thread stays free to time, trace and wait."""

    def __init__(self, door):
        self.door = door
        self.loop = asyncio.new_event_loop()
        self.thread = threading.Thread(target=self.loop.run_forever,
                                       name="front-door", daemon=True)

    def start(self):
        self.thread.start()
        asyncio.run_coroutine_threadsafe(self.door.start(),
                                         self.loop).result(60)

    def stop(self):
        """Drain what is in flight; a server that cannot drain within
        DRAIN_S is stopped without it (its requests are counted lost)."""
        try:
            asyncio.run_coroutine_threadsafe(
                asyncio.wait_for(self.door.stop(drain=True), DRAIN_S),
                self.loop).result(DRAIN_S + 30)
        except TimeoutError:
            asyncio.run_coroutine_threadsafe(_cancel_others(),
                                             self.loop).result(30)
            asyncio.run_coroutine_threadsafe(self.door.stop(drain=False),
                                             self.loop).result(60)
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join(30)
        self.loop.close()


def annotate(obj, attr, name, record=None):
    """Wrap an instance's method in a profiler annotation (the instance's
    class is left as it is)."""
    import jax
    fn = getattr(obj, attr)

    def wrapped(*a, **kw):
        with jax.profiler.TraceAnnotation(devtrace.HOST_PREFIX + name):
            out = fn(*a, **kw)
        if record is not None:
            record()
        return out

    setattr(obj, attr, wrapped)


# ------------------------------------------------------------ metrics
def end_to_end(records, window, drain_end):
    """ttft / tpot percentiles over the requests due in the window. A request that failed counts as having waited until
    the drain gave up on it."""
    t0, t1 = window
    due = [r for r in records if t0 <= r["due"] < t1]
    ok = lambda r: r["finish_reason"] in ("length", "eos")
    ttft = [(r["t_first"] if ok(r) else drain_end) - r["due"] for r in due]
    tpot = [((r["t_last"] - r["t_first"]) / (len(r["tokens"]) - 1))
            if ok(r) else drain_end - r["due"]
            for r in due if not ok(r) or len(r["tokens"]) > 1]
    val = {"ttft_p50_ms": stats.nearest_rank(ttft, 50),
           "ttft_p95_ms": stats.nearest_rank(ttft, 95),
           "tpot_p95_ms": stats.nearest_rank(tpot, 95)}
    out = {k: None if v is None else v * 1e3 for k, v in val.items()}
    return out, len(due), sum(not ok(r) for r in due)


def peaks_for(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise SystemExit(f"device kind {kind!r} is not in bench/peaks.json")
    return table["devices"][kind]


# ------------------------------------------------------------ one run
def open_system(cell, seed, trace, check_chip=True, t_start=None,
                warm_plan=None):
    """Set-up, up to the open front door: runtime, weights, the artifact,
    the engine, the warm-up of every shape the traffic needs. Returns the
    system under test and what set-up recorded, as one namespace."""
    S = types.SimpleNamespace()
    S.cell, S.seed, S.trace = cell, seed, trace
    S.setup = setup = Setup()
    S.t_start = T_START if t_start is None else t_start
    cfg_file, mix = cell["config"], cell["mix"]
    with setup.step("runtime"):
        import jax
        S.devs = jax.devices()
        if check_chip and (S.devs[0].platform != "tpu"
                           or len(S.devs) < cell["chips"]):
            raise NoChip(f"JAX found {len(S.devs)} {S.devs[0].platform} "
                         f"device(s); the cell needs {cell['chips']} TPU")
        CACHE.mkdir(parents=True, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(CACHE))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    S.compile_times = compile_times = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, d, **kw: compile_times.append(time.monotonic())
        if ev == "/jax/core/compile/backend_compile_duration" else None)
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise NoChip(f"no program under {src}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    with setup.step("imports"):
        import numpy as np
        from repro.launch.mesh import make_host_mesh
        from repro.serving import AdmissionController
        from repro.serving.service import (HttpFrontDoor, Service,
                                           ServiceConfig)
        from repro.sharding.ctx import make_ctx
        from repro import telemetry
        S.ref = ref = load_module(BENCH / "reference" /
                                  f"{cfg_file['reference']}.py")
        S.gen = load_module(BENCH / "traffic" / f"{mix['kind']}.py")
    pc = program_config(cfg_file, ref)
    S.dims = dims = ref.Dims.from_hf(cfg_file["model"])
    S.hqp = "hqp" in cfg_file
    S.params = build_params(cfg_file, pc, seed, setup)
    ctx = make_ctx(make_host_mesh(), batch_sharded=False,
                   quantized_kv=S.hqp)
    S.keep, S.served, S.art, S.ref_drop = None, S.params, None, None
    if S.hqp:
        S.art, S.keep, S.ref_drop = compress(cfg_file, pc, ctx, S.params,
                                             ref, dims, setup)
        S.served = S.art.params
        log(f"[setup] artifact {S.art.manifest.bytes_before} -> "
            f"{S.art.manifest.bytes_after} bytes, theta "
            f"{S.art.manifest.theta:.4f}, {S.art.manifest.n_drop} units "
            f"dropped ({S.ref_drop} by the reference)")
    S.work = ref.work_model(dims, S.served, S.hqp)
    with setup.step("engine"):
        S.eng, eargs = build_engine(cfg_file, mix, pc, ctx, S.served)
    wrng = np.random.default_rng([seed % 2 ** 64, 3])
    for name, stages in S.gen.setup_stages(warm_plan, mix, dims.vocab,
                                           wrng):
        warm(S.eng, stages, setup, name)
    S.mem_in_use = max((d.memory_stats() or {}).get("bytes_in_use", 0)
                       for d in S.devs[:S.cell["chips"]])
    S.svc = Service(S.eng, ServiceConfig(queue_depth=eargs.queue_depth,
                                         default_deadline_s=None),
                    admission=AdmissionController())
    S.door = HttpFrontDoor(S.svc, host="127.0.0.1", port=0)
    S.steps, S.tracer = [], None
    if trace:
        S.tracer = telemetry.SpanRecorder()
        S.eng.tracer = S.tracer
        eng = S.eng
        annotate(eng, "step", "engine_step", lambda: S.steps.append(
            {"t": time.monotonic() - eng.last_step["wall_s"],
             "wall_s": eng.last_step["wall_s"],
             "phases": dict(eng.last_step["phases"])}))
        annotate(S.door, "_serve_inbox", "door_inbox")
        annotate(S.door, "_flush", "door_flush")
    S.dt = DoorThread(S.door)
    S.dt.start()
    return S


def serve_window(S, plan, seconds):
    """Send ``plan`` from the load generator child; returns the window,
    the drain's end and the child's records. With ``S.trace`` a stretch
    in the middle of the window is profiled into ``S.tr``."""
    import jax
    child = subprocess.Popen([sys.executable, str(BENCH / "loadgen.py")],
                             stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                             cwd=str(ROOT))
    t0 = time.monotonic() + LEAD_S
    window = (t0, t0 + seconds)
    drain_end = window[1] + DRAIN_S
    plan = dict(plan, port=S.door.port, t0=t0, deadline=drain_end)
    child.stdin.write(json.dumps(plan).encode())
    child.stdin.close()
    child.stdin = None          # communicate() then only reads
    S.tr = None
    try:
        if S.trace:
            time.sleep(max(0.0, t0 + (seconds - TRACE_S) / 2
                           - time.monotonic()))
            tdir = OUT / "trace"
            shutil.rmtree(tdir, ignore_errors=True)
            with devtrace.capture(str(tdir)):
                with jax.profiler.TraceAnnotation(devtrace.HOST_PREFIX +
                                                  "window"):
                    ta = time.monotonic()
                    time.sleep(min(TRACE_S, seconds / 2))
                    tb = time.monotonic()
            S.tr = devtrace.extract(str(tdir), (ta, tb),
                                    S.cell["config"].get("kernels", ()))
            S.trace_window = (ta, tb)
        out, _ = child.communicate(timeout=drain_end - time.monotonic() + 30)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    records = json.loads(out)["records"]
    lag = [r["sent"] - r["due"] for r in records if r["sent"] is not None]
    log(f"[load] {len(records)} requests sent, lag p50 "
        f"{1e3 * (stats.nearest_rank(lag, 50) or 0):.3f} ms max "
        f"{1e3 * max(lag, default=0):.3f} ms; engine stats "
        f"{json.dumps(S.eng.stats)}")
    return window, drain_end, records


def layer_metrics(S, window, result, device):
    """The cell's per-layer metrics from the traced run's records."""
    tr = S.tr
    peaks = peaks_for(S.devs[0].device_kind)
    prompt_len = {s["uid"]: s["args"]["prompt_len"]
                  for s in S.tracer.records
                  if s["type"] == "span" and s["name"] == "request"}
    rec = {"window": window, "spans": S.tracer.records, "steps": S.steps,
           "compile_times": S.compile_times, "trace": tr,
           "n_slots": S.eng.n_slots, "peaks": peaks,
           "work_window": work.count(S.work, S.tracer.records, prompt_len,
                                     *window),
           "work_trace": work.count(S.work, S.tracer.records, prompt_len,
                                    *S.trace_window)}
    units = {m["name"]: m["unit"] for m in S.cell["per_layer"]}
    metrics = {}
    for m in S.cell["per_layer"]:
        v = load_module(BENCH / "metrics" / f"{m['name']}.py").read(rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": units[m["name"]]}
    wt = rec["work_trace"]
    for name, calls in wt.calls.items():
        least, bound = work.least_time(calls,
                                       peaks["flops"][wt.precision[name]],
                                       peaks["hbm_bytes_per_s"])
        log(f"[roofline] {name}: least time {least:.6f}s, bound by {bound}, "
            f"device {devtrace.kernel_seconds(tr, name):.6f}s")
    device.update(busy_s=devtrace.busy_s(tr), window_s=tr["window_s"])
    result["breakdown"] = {"device_ops": devtrace.top_ops(tr),
                           "idle_gaps": devtrace.idle_gaps(tr)}
    return metrics


def verdict(compared) -> bool:
    """Correct where every number compared is within its limit."""
    return all(v["value"] <= v["limit"] for v in compared.values())


def check(S, records, window, control):
    """Free the program's state, then compare with the reference. Returns
    (correct, the numbers compared, the control's numbers or None)."""
    cfg_file = S.cell["config"]
    lost, wrong = correctness.counts(records, window)
    sample = correctness.sample(records, S.seed)
    pruned = (None if S.art is None
              else abs(S.art.manifest.n_drop - S.ref_drop))
    for name in ("eng", "svc", "door", "dt", "served", "art", "tracer"):
        setattr(S, name, None)
    gc.collect()
    ref, params, dims, keep = S.ref, S.params, S.dims, S.keep
    prec = ref.Precision(**cfg_file["precision"]["reference"])
    cprec = ref.Precision(**cfg_file["precision"]["control"])
    t = time.monotonic()
    gap, n_cmp, ctrl_gap = 0.0, 0, 0.0
    length = S.cell["mix"]["engine"]["max_seq"]
    for r in sample:
        g, _ = correctness.gaps(ref, params, dims, keep, prec, r, length)
        gap, n_cmp = max(gap, float(g.max())), n_cmp + len(g)
        if control:
            _, first = correctness.gaps(ref, params, dims, keep, cprec, r,
                                        length)
            cg, _ = correctness.gaps(ref, params, dims, keep, prec, r,
                                     length, served=first)
            ctrl_gap = max(ctrl_gap, float(cg.max()))
    limit = cfg_file["correct"]["max_gap"]

    def numbers(g):
        out = {"max_gap": {"value": g, "limit": limit},
               "lost": {"value": lost, "limit": 0},
               "wrong_length": {"value": wrong, "limit": 0}}
        if pruned is not None:
            out["pruned_units_diff"] = {"value": pruned, "limit": 0}
        return out

    compared = numbers(gap)
    correct = bool(sample) and verdict(compared)
    ctrl = None
    if control:
        ctrl = numbers(ctrl_gap)
        ctrl = {"correct": bool(sample) and verdict(ctrl),
                "max_gap": ctrl_gap}
    log(f"[check] reference over {len(sample)} requests, {n_cmp} tokens, "
        f"{time.monotonic() - t:.3f}s" + (f"; control {ctrl}"
                                         if control else ""))
    return correct, compared, ctrl


def run_cell(cell, seed, seconds, trace, control=False, check_chip=True,
             t_start=None):
    """One run of one cell; returns the result line's object."""
    gen = load_module(BENCH / "traffic" / f"{cell['mix']['kind']}.py")
    vocab = cell["config"]["model"]["vocab_size"]
    plan = gen.generate(cell["mix"], seed, seconds, vocab)
    S = open_system(cell, seed, trace, check_chip, t_start, warm_plan=plan)
    setup_s = time.monotonic() + LEAD_S - S.t_start
    log("[setup] " + " ".join(f"{k}={v:.3f}s" for k, v in
                              S.setup.steps.items()) +
        f" total={setup_s:.3f}s compiles={len(S.compile_times)}")
    window, drain_end, records = serve_window(S, plan, seconds)
    S.dt.stop()
    e2e, attempted, failed = end_to_end(records, window, drain_end)
    dev = S.devs[:cell["chips"]]
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in dev)
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev), "memory_peak_bytes": int(mem),
              "memory_in_use_after_setup_bytes": int(S.mem_in_use)}
    result = {"correct": None, "attempted": attempted, "failed": failed}
    if trace:
        metrics = layer_metrics(S, window, result, device)
    else:
        e2e["setup_s"] = window[0] - S.t_start
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"]
                   if e2e.get(m["name"]) is not None}
    result["metrics"] = metrics
    result["device"] = device
    correct, compared, ctrl = check(S, records, window, control)
    result["correct"] = correct
    if control:
        result["control"] = ctrl
    result["compared"] = compared
    for k, v in compared.items():
        log(f"compared {k} {v['value']} limit {v['limit']}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also read the control's gap (limit setting)")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    try:
        result = run_cell(cell, args.seed, args.seconds, args.trace,
                          control=bool(args.control))
    except NoChip as e:
        log(f"[bench] {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
