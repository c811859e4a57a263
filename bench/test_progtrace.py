"""Self-test of the reduction of the program's own trace names.

    python -m pytest -q bench/test_progtrace.py

Each reduction on a synthetic record whose answer is known; ``extract`` on
a profile taken here of annotations the way the program writes them, and
the HLO decoder on a profile of a small jitted step taken here; ``extract``
and the HLO decoder on ``testdata/progtrace_v5e.xplane.pb.gz``, a raw
profile from the chip; and every reduction on
``testdata/progtrace_v5e.json``: ``progtrace.extract`` of a ``--trace 1``
run of ``hqp-chat`` on one TPU v5e, its device operations cut to 40 ms in
which the engine both decodes and prefills, every annotation of the 3 s
stretch kept.
"""
import gzip
import json
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent))

from bench import devtrace, progtrace  # noqa: E402


def op(t, dur, module="jit_engine_decode", scope=None, name="fusion.1",
       pool=False):
    return {"device": "/device:TPU:0", "name": name, "module": module,
            "scope": scope, "pool": pool, "t": t, "dur": dur}


def ann(name, t, dur, thread="pump", **args):
    args.setdefault("t_engine", t + 100.0)
    return {"name": name, "thread": thread, "t": t, "dur": dur,
            "args": {k: str(v) for k, v in args.items()}}


def record(ops=(), anns=(), window_s=1.0):
    return {"window_s": window_s, "ops": list(ops), "ann": list(anns)}


def test_prefill_device_share_counts_prefill_executables():
    rec = record([op(0.0, 0.2, "jit_engine_prefill"),
                  op(0.1, 0.2, "jit_engine_prefill"),      # overlaps: 0.3
                  op(0.4, 0.1, "jit_engine_spec_prefill"),
                  op(0.5, 0.4, "jit_engine_decode")])
    assert progtrace.prefill_device_share(rec) == pytest.approx(
        100 * 0.4 / 0.8)
    assert progtrace.prefill_device_share(
        record([op(0.0, 0.5)])) is None          # no prefill module named


def test_kv_pool_share_counts_kv_scopes():
    """kv_* scopes and moves of the executable's state count; a move of
    anything else (a layer's weights) does not."""
    rec = record([op(0.0, 0.1, scope="kv_write"),
                  op(0.1, 0.2, scope="kv_select"),
                  op(0.3, 0.3, scope="attention"),
                  op(0.6, 0.1, scope=None, name="copy.3"),
                  op(0.7, 0.3, scope=None, name="copy.4", pool=True)])
    assert progtrace.kv_pool_share(rec) == pytest.approx(60.0)
    assert progtrace.kv_pool_share(record([op(0, 1, scope="mlp")])) is None


def test_host_bound_idle_share_leaves_out_the_parked_pump():
    # device busy 0-0.2 and 0.5-0.6: idle 0.2-0.5 and 0.6-1.0 (0.7 s);
    # the pump parks 0.3-0.45 and 0.8-1.2 (0.15 + 0.2 of the idle time)
    rec = record([op(0.0, 0.2), op(0.5, 0.1)],
                 [ann("repro.door.idle", 0.3, 0.15),
                  ann("repro.door.idle", 0.8, 0.4),
                  ann("repro.engine.step", 0.1, 0.3, thread="engine")])
    assert progtrace.host_bound_idle_share(rec) == pytest.approx(
        100 * (0.7 - 0.35))
    assert progtrace.host_bound_idle_share(
        record([op(0, 0.5)], [ann("repro.engine.step", 0, 1)])) is None


def test_flush_delay_p95_is_nearest_rank_over_the_stretch():
    flushes = [ann("repro.door.flush", 0.01 * i, 0.001, thread="loop",
                   t_staged=10.0, t_engine=10.0 + 0.001 * (i + 1))
               for i in range(20)]
    late = ann("repro.door.flush", 2.0, 0.001, t_staged=0.0, t_engine=9.0)
    rec = record(anns=flushes + [late])           # the late one is outside
    assert progtrace.flush_delay_p95_ms(rec) == pytest.approx(19.0)
    assert progtrace.flush_delay_p95_ms(record()) is None


def test_clock_fit_recovers_one_offset():
    jitter = [0.0, 2e-6, -1e-6, 4e-6, 1e-6]
    anns = [ann("repro.engine.admit", 0.1 * i + j, 0.01,
                t_engine=0.1 * i - 5.0) for i, j in enumerate(jitter)]
    off, worst, n = progtrace.clock_fit(record(anns=anns))
    mean = sum(jitter) / len(jitter)
    assert off == pytest.approx(5.0 + mean, abs=1e-12)
    assert worst == pytest.approx(max(abs(j - mean) for j in jitter))
    assert n == len(jitter)


def test_idle_goes_to_the_innermost_open_annotation():
    # device idle 0.2-1.0. On the engine thread a step 0.1-0.9 holds a
    # host sync 0.3-0.5; the loop flushes 0.4-0.6. The host sync, one level
    # deeper, beats the flush over 0.4-0.5; over 0.5-0.6 the flush, opened
    # after the step, beats it; nothing is open over 0.9-1.0.
    rec = record([op(0.0, 0.2)],
                 [ann("repro.engine.step", 0.1, 0.8, thread="engine"),
                  ann("repro.engine.host_sync", 0.3, 0.2, thread="engine"),
                  ann("repro.door.flush", 0.4, 0.2, thread="loop")])
    assert progtrace.idle_by_annotation(rec) == pytest.approx(
        {"repro.engine.step": 0.4, "repro.engine.host_sync": 0.2,
         "repro.door.flush": 0.1, "none": 0.1})


@pytest.mark.parametrize("name,move", [
    ("copy.140", True), ("copy-start.5", True), ("copy-done", True),
    ("constant_dynamic-slice_fusion.2", True),
    ("dynamic-slice_bitcast_fusion", True),
    ("constant_dynamic-update-slice_fusion.7", True),
    ("fusion.12", False), ("paged_prefill_attention_pallas.3", False),
    ("iota_reduce_fusion", False), ("convert_bitcast_fusion.1", False)])
def test_moves_are_told_by_name(name, move):
    assert progtrace.is_move(name) is move


def test_moves_split_by_module_and_scope():
    rec = record([op(0.0, 0.1, "jit_engine_decode", None, "copy.1"),
                  op(0.1, 0.2, "jit_engine_prefill", "kv_scatter",
                     "dynamic-update-slice.3"),
                  op(0.3, 0.3, "jit_engine_decode", "attention",
                     "paged_prefill_attention_pallas.1")])
    by_mod, by_scope = progtrace.moves_by(rec)
    assert by_mod == pytest.approx({"jit_engine_decode": 0.1,
                                    "jit_engine_prefill": 0.2})
    assert by_scope == pytest.approx({"unscoped": 0.1, "kv_scatter": 0.2})


def test_innermost_scope_reads_the_op_name_path():
    f = progtrace.innermost_scope
    assert f("jit(engine_decode)/while/body/attention/kv_write/scatter") \
        == "kv_write"
    assert f("jit(engine_prefill)/kv_gather/dynamic_slice") == "kv_gather"
    assert f("jit(engine_decode)/while/body/add") is None


# ------------------------------------------------- a profile taken here
def _profile(tmp_path, annotate):
    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(devtrace.HOST_PREFIX + "window"):
            annotate(jax)
    finally:
        jax.profiler.stop_trace()
    return progtrace.extract(str(tmp_path))


def test_extract_reads_annotations_and_their_arguments(tmp_path):
    def annotate(jax):
        with jax.profiler.TraceAnnotation("repro.door.flush", t_staged=1.5,
                                          t_engine=2.0):
            with jax.profiler.TraceAnnotation("repro.engine.admit",
                                              t_engine=2.25):
                pass

    rec = _profile(tmp_path, annotate)
    names = {a["name"]: a for a in rec["ann"]}
    assert set(names) == {"repro.door.flush", "repro.engine.admit"}
    assert float(names["repro.door.flush"]["args"]["t_staged"]) == 1.5
    assert float(names["repro.engine.admit"]["args"]["t_engine"]) == 2.25
    assert all(0.0 <= a["t"] <= rec["window_s"] for a in rec["ann"])
    assert progtrace.flush_delay_p95_ms(rec) == pytest.approx(500.0)


def test_hlo_programs_reads_scopes_and_moves_of_the_state(tmp_path):
    """The HLO decoder on the bytes of a profile taken here: a jitted step
    that scans over a donated, layer-stacked arena (the engine's pool in
    small) and over stacked weights, writing a scope of its own."""
    import jax
    import jax.numpy as jnp

    def engine_step(arena, weights, x):
        def layer(h, xs):
            cache, w = xs
            with jax.named_scope("kv_write"):
                cache = cache.at[0].set(h[0])
            return jnp.tanh(h @ w), cache
        with jax.named_scope("logits"):
            h, arena = jax.lax.scan(layer, x, (arena, weights))
        return arena, h

    step = jax.jit(engine_step, donate_argnums=(0,))
    arena = jnp.zeros((3, 64, 8, 16), jnp.float32)
    weights = jnp.ones((3, 16, 16), jnp.float32)
    x = jnp.ones((8, 16), jnp.float32)
    arena, _ = step(arena, weights, x)         # compiled before the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(step(arena, weights, x))
    finally:
        jax.profiler.stop_trace()
    f = progtrace._newest(str(tmp_path))
    progs = progtrace.hlo_programs(pathlib.Path(f).read_bytes())
    mine = [p for p in progs.values()
            if any(s == "kv_write" for s, _ in p.values())]
    assert len(mine) == 1, progs
    ops = mine[0]
    assert {s for s, _ in ops.values()} >= {"kv_write", "logits"}
    moved = {name for name, (_, pool) in ops.items() if pool}
    assert moved and all(progtrace.is_move(n) for n in moved), ops
    # the layer's weights are sliced out of their stack too: a move, and
    # not of the state
    assert any(progtrace.is_move(n) and not pool
               for n, (_, pool) in ops.items()), ops


def test_extract_of_a_program_that_names_nothing_is_none(tmp_path):
    assert _profile(tmp_path, lambda jax: None) is None


# ------------------------------------------------- a raw profile from the chip
@pytest.fixture(scope="module")
def raw_chip(tmp_path_factory):
    """The ``.xplane.pb`` of the program's engine at qwen3-0.6b's widths cut
    to 2 layers (INT8 weights and KV, the Pallas kernels) behind the HTTP
    front door on one TPU v5e, a few requests in flight: 20 ms of it, with
    the programs that ran in them."""
    f = tmp_path_factory.mktemp("raw") / "chip.xplane.pb"
    f.write_bytes(gzip.decompress(
        (BENCH / "testdata" / "progtrace_v5e.xplane.pb.gz").read_bytes()))
    return f


def test_raw_chip_profile_programs_carry_scopes_and_state(raw_chip):
    progs = progtrace.hlo_programs(raw_chip.read_bytes())
    assert len(progs) == 9
    assert {s for p in progs.values() for s, _ in p.values()} == {
        None, "kv_gather", "kv_scatter", "kv_select", "kv_write",
        "attention", "mlp", "logits", "sample"}
    moved = [n for p in progs.values() for n, (_, pool) in p.items()
             if pool]
    assert moved and all(progtrace.is_move(n) for n in moved)


def test_raw_chip_profile_extracts_whole(raw_chip):
    rec = progtrace.extract(str(raw_chip))
    assert {"repro.engine.step", "repro.engine.admit",
            "repro.engine.prefill_dispatch", "repro.engine.decode_scan",
            "repro.engine.host_sync", "repro.engine.token_fanout",
            "repro.service.step", "repro.door.inbox",
            "repro.door.flush"} <= {a["name"] for a in rec["ann"]}
    assert all("t_engine" in a["args"] for a in rec["ann"])
    assert all(op["module"] for op in rec["ops"])
    assert {"jit_engine_prefill", "jit_engine_decode"} <= {
        op["module"] for op in rec["ops"]}
    assert any(op["pool"] and op["scope"] is None for op in rec["ops"])
    for f in (progtrace.prefill_device_share, progtrace.kv_pool_share):
        assert 0.0 < f(rec) < 100.0, f.__name__
    assert progtrace.flush_delay_p95_ms(rec) > 0.0
    off, worst, n = progtrace.clock_fit(rec)
    assert n == len(rec["ann"]) and worst < 50e-6


# ------------------------------------------------- a stretch from the chip
@pytest.fixture(scope="module")
def chip():
    return json.loads((BENCH / "testdata" / "progtrace_v5e.json")
                      .read_text())


def test_chip_stretch_every_operation_has_an_executable(chip):
    assert {op["device"] for op in chip["ops"]} == {"/device:TPU:0"}
    # besides the engine's executables, the prefill tail's ``logits[0]``
    # runs as two small ones that JAX names
    mods = {op["module"] for op in chip["ops"]}
    assert {"jit_engine_decode", "jit_engine_prefill"} <= mods <= {
        "jit_engine_decode", "jit_engine_prefill", "jit_engine_reset",
        "jit_dynamic_slice", "jit_squeeze"}
    per = [progtrace.busy_s(chip, lambda o: o["module"] == m) for m in mods]
    assert min(per) > 0
    assert sum(per) == pytest.approx(devtrace.busy_s(chip), rel=1e-6)


def test_chip_stretch_metrics_are_shares_of_the_stretch(chip):
    for f in (progtrace.prefill_device_share, progtrace.kv_pool_share,
              progtrace.host_bound_idle_share):
        assert 0.0 < f(chip) < 100.0, f.__name__
    assert progtrace.flush_delay_p95_ms(chip) > 0.0
    idle = chip["window_s"] - devtrace.busy_s(chip)
    assert progtrace.host_bound_idle_share(chip) <= \
        100.0 * idle / chip["window_s"] + 1e-9


def test_chip_stretch_idle_and_moves_add_up(chip):
    by_ann = progtrace.idle_by_annotation(chip)
    assert sum(by_ann.values()) == pytest.approx(
        chip["window_s"] - devtrace.busy_s(chip), rel=1e-6)
    assert set(by_ann) <= {a["name"] for a in chip["ann"]} | {"none"}
    by_mod, by_scope = progtrace.moves_by(chip)
    assert sum(by_mod.values()) == pytest.approx(sum(by_scope.values()))
    assert 0 < sum(by_mod.values()) <= sum(b - a for a, b, _ in
                                           devtrace.clip(chip))
    assert set(by_scope) <= set(progtrace.SCOPES) | {"unscoped"}


def test_chip_stretch_kv_pool_is_scopes_and_moves_of_the_state(chip):
    """The arena's slices, write-backs and copies carry no program scope,
    yet are the pool's: they are told by shape, and only moves are."""
    state = [op for op in chip["ops"] if op["pool"]]
    assert state and all(progtrace.is_move(op["name"]) and op["module"] in
                         ("jit_engine_decode", "jit_engine_prefill")
                         for op in state)
    assert any(op["scope"] is None for op in state)
    scoped = progtrace.busy_s(chip, lambda op: (op["scope"] or "")
                              .startswith("kv_"))
    assert progtrace.busy_s(chip, progtrace.in_pool) > 2 * scoped


def test_chip_stretch_clock_fit(chip):
    """Over every annotation of the 3 s stretch."""
    off, worst, n = progtrace.clock_fit(chip)
    assert n == len(chip["ann"]) > 400
    assert worst < 50e-6
    for line in progtrace.summary_lines(chip):
        assert line.startswith("[progtrace] ")
