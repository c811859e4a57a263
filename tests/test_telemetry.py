"""Telemetry plane: histogram math, exposition, spans, phase attribution.

The load-bearing guarantees (DESIGN.md §16):
  * histogram buckets follow Prometheus ``le`` semantics — inclusive
    upper edges, an implicit +Inf overflow — and merge bucket-wise only
    when the edges match;
  * ``MetricsRegistry.render`` emits well-formed text exposition v0.0.4
    (golden-tested), ``parse_exposition`` round-trips it exactly, and
    label escaping survives backslash/quote/newline;
  * adopted stats dicts stay the writable source of truth: the registry
    reads live values at render time and REJECTS undeclared keys;
  * the span recorder gives every submitted uid exactly one terminal,
    and ``queued + active`` tiles the ``request`` envelope — including
    under injected engine faults and admit/cancel/expiry storms;
  * ``GET /metrics`` on the front door serves the full declared metric
    set mid-conversation;
  * ``Engine.last_step`` is the single measurement source: the service
    feeds the phase histograms and the admission EWMAs from it, through
    one injected clock shared by engine, service, and recorder.
"""
import asyncio
import json

import jax
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:      # bare container: skip property tests
    from _hypothesis_stub import given, settings, st

from repro import configs
from repro.models import lm
from repro.serving import (Engine, HttpFrontDoor, Request, SchedulerConfig,
                           Service, ServiceConfig, faults)
from repro.telemetry import (Histogram, MetricsRegistry, SpanRecorder,
                             escape_label, parse_exposition, schema)

ARCH = "qwen3-0.6b"


# ------------------------------------------------------- histogram math
def test_histogram_le_edges_are_inclusive():
    h = Histogram("h", buckets=(1.0, 2.0, 4.0))
    for v, bucket in ((0.5, 0), (1.0, 0), (1.5, 1), (2.0, 1),
                      (2.0000001, 2), (4.0, 2), (4.5, 3), (100.0, 3)):
        before = list(h.counts)
        h.observe(v)
        assert h.counts[bucket] == before[bucket] + 1, \
            f"{v} should land in bucket {bucket} (le semantics)"
    assert h.count == 8 and h.counts[-1] == 2     # +Inf overflow holds 2
    assert h.sum == pytest.approx(0.5 + 1.0 + 1.5 + 2.0 + 2.0000001
                                  + 4.0 + 4.5 + 100.0)


def test_histogram_merge_and_dict_roundtrip():
    a = Histogram("h", buckets=(1.0, 2.0))
    b = Histogram("h", buckets=(1.0, 2.0))
    for v in (0.5, 1.5, 3.0):
        a.observe(v)
        b.observe(v)
    a.merge(b)
    assert a.counts == [2, 2, 2] and a.count == 6
    assert a.sum == pytest.approx(10.0)
    c = Histogram.from_dict(a.to_dict())
    assert (c.counts, c.count, c.sum) == (a.counts, a.count, a.sum)
    assert c.edges == a.edges
    with pytest.raises(ValueError):
        a.merge(Histogram("h", buckets=(1.0, 3.0)))
    with pytest.raises(ValueError):
        Histogram("h", buckets=(2.0, 1.0))        # not increasing
    with pytest.raises(ValueError):
        Histogram.from_dict({"le": [1.0, 2.0], "counts": [1]})


def test_histogram_quantile_reports_bucket_upper_edge():
    h = Histogram("h", buckets=tuple(float(i) for i in range(1, 11)))
    for v in range(1, 11):                        # one per bucket
        h.observe(v - 0.5)
    assert h.quantile(0.0) == 1.0
    assert h.quantile(0.5) == 5.0
    assert h.quantile(1.0) == 10.0
    assert Histogram("h", buckets=(1.0,)).quantile(0.5) == 0.0
    with pytest.raises(ValueError):
        h.quantile(1.5)


def test_log_buckets_shape():
    edges = schema.log_buckets(1e-3, 1.0, per_decade=2)
    assert len(edges) == 7                        # 3 decades x 2 + 1
    assert edges[0] == pytest.approx(1e-3) and edges[-1] == pytest.approx(1.0)
    ratios = [b / a for a, b in zip(edges, edges[1:])]
    assert all(r == pytest.approx(ratios[0]) for r in ratios)
    with pytest.raises(ValueError):
        schema.log_buckets(1.0, 0.1)


# ----------------------------------------------------------- exposition
def test_exposition_golden():
    reg = MetricsRegistry()
    reg.counter("t_total", "total things").inc(2)
    reg.gauge("t_jobs", "live jobs").set(3)
    h = reg.histogram("t_hist", "timings", buckets=(0.1, 1.0), phase="x")
    for v in (0.0625, 0.5, 4.0):
        h.observe(v)
    assert reg.render() == (
        "# HELP t_hist timings\n"
        "# TYPE t_hist histogram\n"
        't_hist_bucket{le="0.1",phase="x"} 1\n'
        't_hist_bucket{le="1",phase="x"} 2\n'
        't_hist_bucket{le="+Inf",phase="x"} 3\n'
        't_hist_sum{phase="x"} 4.5625\n'
        't_hist_count{phase="x"} 3\n'
        "# HELP t_jobs live jobs\n"
        "# TYPE t_jobs gauge\n"
        "t_jobs 3\n"
        "# HELP t_total total things\n"
        "# TYPE t_total counter\n"
        "t_total 2\n")


def test_exposition_parse_roundtrip_and_strictness():
    reg = MetricsRegistry()
    reg.counter("c_one", "a counter").inc(7)
    h = reg.histogram("h_one", "a histogram", buckets=(1.0, 2.0))
    h.observe(1.5)
    parsed = parse_exposition(reg.render())
    assert parsed["types"] == {"c_one": "counter", "h_one": "histogram"}
    s = parsed["samples"]
    assert s[("c_one", ())] == 7
    assert s[("h_one_bucket", (("le", "1"),))] == 0
    assert s[("h_one_bucket", (("le", "2"),))] == 1      # cumulative
    assert s[("h_one_bucket", (("le", "+Inf"),))] == 1
    assert s[("h_one_sum", ())] == 1.5
    assert s[("h_one_count", ())] == 1
    with pytest.raises(ValueError):
        parse_exposition("this is not a sample line at all!\n")
    with pytest.raises(ValueError):
        parse_exposition('m{le="1" garbage} 3\n')


def test_label_escaping_survives_roundtrip():
    nasty = 'back\\slash "quoted"\nnewline'
    assert escape_label(nasty) == \
        'back\\\\slash \\"quoted\\"\\nnewline'
    reg = MetricsRegistry()
    reg.gauge("g_esc", "escaped", tag=nasty).set(1)
    parsed = parse_exposition(reg.render())
    assert parsed["samples"] == {("g_esc", (("tag", nasty),)): 1.0}


def test_register_stats_rejects_undeclared_and_reads_live():
    reg = MetricsRegistry()
    stats = {"submitted": 0}
    reg.register_stats(schema.SERVICE_PREFIX, stats, schema.SERVICE_STATS)
    stats["submitted"] += 41                      # live dict stays writable
    stats["submitted"] += 1
    parsed = parse_exposition(reg.render())
    assert parsed["samples"][(schema.SERVICE_PREFIX + "submitted", ())] == 42
    with pytest.raises(ValueError, match="not_declared"):
        reg.register_stats(schema.SERVICE_PREFIX, {"not_declared": 0},
                           schema.SERVICE_STATS)
    with pytest.raises(ValueError, match="duplicate"):
        reg.gauge("g_dup", "x")
        reg.gauge("g_dup", "x")


# -------------------------------------------------------- span recorder
def _lifecycle_ok(rec: SpanRecorder, uids):
    """Exactly one terminal per uid; queued+active tile request exactly
    (same injected timestamps on both sides, so equality, not 5%)."""
    assert rec.open_uids() == []
    assert sorted(rec.terminals) == sorted(uids)
    by_uid = {}
    for r in rec.records:
        if r.get("uid") is not None:
            by_uid.setdefault(r["uid"], []).append(r)
    for uid in uids:
        recs = by_uid[uid]
        fins = [r for r in recs if r["type"] == "instant"
                and r["name"] == "finish"]
        assert len(fins) == 1 and "duplicate" not in fins[0]["args"], \
            f"uid {uid}: {fins}"
        assert fins[0]["args"]["reason"] in schema.TERMINAL_REASONS
        req = [r for r in recs if r["type"] == "span"
               and r["name"] == "request"]
        assert len(req) == 1
        parts = [r for r in recs if r["type"] == "span"
                 and r["name"] in ("queued", "active")]
        part_dur = sum(r["t1"] - r["t0"] for r in parts)
        req_dur = req[0]["t1"] - req[0]["t0"]
        assert part_dur == pytest.approx(req_dur), f"uid {uid} not tiled"
        parts.sort(key=lambda r: r["t0"])
        for a, b in zip(parts, parts[1:]):
            assert b["t0"] >= a["t1"], f"uid {uid}: overlapping spans"


def test_span_recorder_lifecycle_unit():
    rec = SpanRecorder()
    rec.submit(0, 1.0, prompt_len=8)
    rec.submit(1, 1.5, prompt_len=4)
    rec.admit(0, 2.0, slot=0)
    rec.span("prefill", 0, 2.0, 2.5, lo=0, hi=8, tokens=1)
    rec.first_token(0, 2.5)
    rec.span("decode", 0, 2.5, 3.0, tokens=3, k_steps=4)
    assert rec.open_uids() == [0, 1]
    rec.finish(0, 3.0, "length", n_tokens=4, pages_held=2)
    rec.finish(1, 3.5, "cancelled")               # evicted while queued
    rec.shed(4.0, "saturated")
    _lifecycle_ok(rec, [0, 1])
    assert rec.terminals == {0: "length", 1: "cancelled"}
    assert rec.sheds == 1
    fin0 = [r for r in rec.records if r["type"] == "instant"
            and r["name"] == "finish" and r["uid"] == 0][0]
    assert fin0["args"]["span_tokens"] == 4       # prefill tail + decode
    assert fin0["args"]["pages_held"] == 2
    # never-admitted uid 1: queued alone covers the envelope
    q1 = [r for r in rec.records if r["name"] == "queued"
          and r["uid"] == 1][0]
    assert (q1["t0"], q1["t1"]) == (1.5, 3.5)

    # a double-finish is recorded as an anomaly, never a second terminal
    rec.finish(0, 9.0, "error")
    assert rec.terminals[0] == "length"
    dupes = [r for r in rec.records if r["args"].get("duplicate")]
    assert len(dupes) == 1 and dupes[0]["uid"] == 0


def test_chrome_trace_export_shape():
    rec = SpanRecorder()
    rec.submit(3, 1.0, prompt_len=8)
    rec.admit(3, 2.0, slot=0)
    rec.span("step", None, 1.0, 1.1, total=0.1)
    rec.finish(3, 3.0, "length", n_tokens=0)
    trace = rec.to_chrome_trace()
    evs = trace["traceEvents"]
    names = {e["tid"]: e["args"]["name"] for e in evs if e["ph"] == "M"
             and e["name"] == "thread_name"}
    assert names == {0: "engine", 4: "req 3"}     # tid = uid + 1
    xs = [e for e in evs if e["ph"] == "X"]
    assert all(e["dur"] >= 0 for e in xs)
    step = [e for e in xs if e["name"] == "step"][0]
    assert step["tid"] == 0
    assert step["ts"] == pytest.approx(1.0e6)     # microseconds
    req = [e for e in xs if e["name"] == "request"][0]
    assert req["tid"] == 4 and req["args"]["uid"] == 3
    # jsonl round-trips through plain json
    lines = [json.loads(x) for x in rec.to_jsonl().splitlines()]
    assert lines == rec.records


# ------------------------------------------------------- live engine
@pytest.fixture(scope="module")
def setup():
    cfg = configs.get_smoke_config(ARCH)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    eng = Engine(params, cfg, n_slots=2, max_seq=64,
                 sched=SchedulerConfig(prefill_chunk=8),
                 page_size=8, prefix_cache=False)
    return cfg, eng


def _prompts(cfg, lens, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size, n).tolist() for n in lens]


def _ticking_clock(dt=1e-4):
    now = [0.0]

    def clk():
        now[0] += dt
        return now[0]
    return now, clk


def test_live_stats_keys_are_all_declared(setup):
    """Schema completeness against the LIVE objects: every key the engine
    and service actually carry is declared (the lint rule catches writes;
    this catches declared-but-renamed drift)."""
    cfg, eng = setup
    svc = Service(eng, ServiceConfig(queue_depth=2))
    assert set(eng.stats) <= set(schema.ENGINE_STATS)
    assert set(svc.stats) <= set(schema.SERVICE_STATS)
    assert svc.registry is not None
    assert eng.clock is svc.clock                 # one clock, re-pointed
    # every declared family renders before any traffic
    parsed = parse_exposition(svc.render_metrics())
    assert set(schema.metric_names()) <= set(parsed["types"])


def test_last_step_feeds_phase_hists_and_latency(setup):
    cfg, eng = setup
    now, clk = _ticking_clock()
    svc = Service(eng, ServiceConfig(queue_depth=4), clock=clk)
    t = svc.submit(Request(prompt=_prompts(cfg, [10], seed=2)[0],
                           max_new_tokens=3))
    steps = 0
    while svc.has_work:
        svc.step()
        steps += 1
    assert t.finish_reason == "length"

    last = eng.last_step
    assert last is not None and last["wall_s"] > 0
    assert set(last["phases"]) <= set(schema.PHASES)
    assert "total" in last["phases"]
    # phases nest inside the step: their sum never exceeds the wall time
    parts = sum(v for k, v in last["phases"].items() if k != "total")
    assert parts <= last["phases"]["total"] + 1e-9

    th = svc._phase_hists["total"]
    assert th.count == steps                      # one observation per step
    assert th.sum > 0
    assert svc._ttft_hist.count == 1 and svc._latency_hist.count == 1
    assert svc._latency_hist.sum == pytest.approx(t.latency_s)
    assert svc._ttft_hist.sum == pytest.approx(t.ttft_s)
    # the rendered exposition carries the same numbers
    s = parse_exposition(svc.render_metrics())["samples"]
    assert s[(schema.LATENCY_HISTOGRAM + "_count", ())] == 1
    assert s[(schema.PHASE_HISTOGRAM + "_count",
              (("phase", "total"),))] == steps


def test_spans_one_terminal_under_faults_and_cancel(setup):
    """Chaos-adjacent lifecycle: an injected decode fault and a client
    cancel both land exactly one terminal per uid, and the tiling
    invariant holds on the recorder the engine actually fed."""
    cfg, eng = setup
    rec = eng.tracer = SpanRecorder()
    try:
        now, clk = _ticking_clock()
        svc = Service(eng, ServiceConfig(queue_depth=4), clock=clk)
        h = faults.inject_decode_fault(eng, at=1)
        try:
            a = svc.submit(Request(prompt=_prompts(cfg, [7], seed=3)[0],
                                   max_new_tokens=4))
            b = svc.submit(Request(prompt=_prompts(cfg, [9], seed=3)[0],
                                   max_new_tokens=4))
            while svc.has_work:
                svc.step()
        finally:
            h.restore()
        assert h.fired == 1
        assert a.finish_reason == "error" and b.finish_reason == "error"

        c = svc.submit(Request(prompt=_prompts(cfg, [8], seed=4)[0],
                               max_new_tokens=6))
        svc.step()                                # admit + first chunk
        assert svc.cancel(c.uid)
        svc.drain()

        uids = [a.uid, b.uid, c.uid]
        _lifecycle_ok(rec, uids)
        assert rec.terminals[a.uid] == "error"
        assert rec.terminals[b.uid] == "error"
        assert rec.terminals[c.uid] == "cancelled"
    finally:
        eng.tracer = None


def test_metrics_route_on_front_door(setup):
    cfg, eng = setup
    svc = Service(eng, ServiceConfig(queue_depth=4))
    door = HttpFrontDoor(svc, host="127.0.0.1", port=0)
    prompt = _prompts(cfg, [7], seed=5)[0]

    async def _http(port, method, path, body=b""):
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        writer.write((f"{method} {path} HTTP/1.1\r\nHost: t\r\n"
                      f"Content-Length: {len(body)}\r\n\r\n").encode()
                     + body)
        await writer.drain()
        raw = await reader.read()
        writer.close()
        return raw

    async def scenario():
        await door.start()
        body = json.dumps({"prompt": prompt, "max_new_tokens": 3}).encode()
        raw = await asyncio.wait_for(
            _http(door.port, "POST", "/v1/generate", body), timeout=120)
        assert raw.startswith(b"HTTP/1.1 200")

        raw = await asyncio.wait_for(
            _http(door.port, "GET", "/metrics"), timeout=30)
        head, _, payload = raw.partition(b"\r\n\r\n")
        assert head.startswith(b"HTTP/1.1 200")
        assert b"text/plain" in head and b"version=0.0.4" in head
        await asyncio.wait_for(door.stop(drain=True), timeout=60)
        return payload.decode()

    exposition = asyncio.run(scenario())
    parsed = parse_exposition(exposition)
    assert set(schema.metric_names()) <= set(parsed["types"])
    s = parsed["samples"]
    assert s[(schema.SERVICE_PREFIX + "completed", ())] == 1
    assert s[(schema.LATENCY_HISTOGRAM + "_count", ())] == 1
    assert s[(schema.ENGINE_PREFIX + "accepted_tokens", ())] >= 3


# --------------------------------------------------- lifecycle property
_STORM = {}


def _storm_setup():
    if not _STORM:
        cfg = configs.get_smoke_config(ARCH)
        params = lm.init_params(jax.random.PRNGKey(0), cfg)
        _STORM["cfg"] = cfg
        _STORM["eng"] = Engine(params, cfg, n_slots=2, max_seq=64,
                               sched=SchedulerConfig(prefill_chunk=8),
                               page_size=8, prefix_cache=False)
    return _STORM["cfg"], _STORM["eng"]


@settings(deadline=None, max_examples=10)
@given(st.lists(st.tuples(st.integers(0, 4), st.integers(0, 6)),
                max_size=14))
def test_span_lifecycle_property_under_storm(ops):
    """Random admit / deadline-admit / expiry / cancel / fault
    interleavings: every uid the engine ever saw ends in exactly one
    terminal and queued+active tile its envelope — the recorder never
    loses a request, whatever kills it."""
    cfg, eng = _storm_setup()
    rec = eng.tracer = SpanRecorder()
    fault = None
    try:
        now = [0.0]
        svc = Service(eng, ServiceConfig(queue_depth=3),
                      clock=lambda: now[0])
        rng = np.random.RandomState(23)
        uids = []
        for op, n in ops:
            if op in (0, 1):
                t = svc.submit(
                    Request(prompt=rng.randint(0, cfg.vocab_size,
                                               5 + n).tolist(),
                            max_new_tokens=1 + n % 4),
                    deadline_s=0.5 * (n + 1) if op == 1 else None)
                if t is not None:
                    uids.append(t.uid)
            elif op == 2:
                now[0] += 0.6 * (n + 1)
            elif op == 3 and svc.tickets:
                svc.cancel(sorted(svc.tickets)[n % len(svc.tickets)])
            elif op == 4 and fault is None:
                fault = faults.inject_decode_fault(eng, at=1 + n % 2)
            svc.step()
        svc.drain()
        _lifecycle_ok(rec, uids)
    finally:
        if fault is not None:
            fault.restore()
        eng.tracer = None
