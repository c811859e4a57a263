"""The program's own profiler trace: phase annotations, executable names,
scopes.

  * one engine tick driven through ``Service`` and one pump iteration of
    ``HttpFrontDoor`` leave ``repro.engine.*``, ``repro.service.*`` and
    ``repro.door.*`` host events in the ``.xplane.pb`` that
    ``jax.profiler`` writes, each carrying ``t_engine``, the engine-clock
    reading at which it opened;
  * ``Engine.last_step["phases"]`` keeps its keys and, on a deterministic
    clock, its values whether or not a profiler session is active;
  * the engine's jitted functions carry stable names, and the HLO of a
    prefill and a decode step carries the model step's scopes in its
    ``op_name`` metadata.
"""
import asyncio
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import configs
from repro.analysis.hlo_checks import engine_hot_paths
from repro.models import lm
from repro.serving import (Engine, HttpFrontDoor, Request, SchedulerConfig,
                           Service, ServiceConfig)
from repro.telemetry import schema

ARCH = "qwen3-0.6b"


@pytest.fixture(scope="module")
def setup():
    cfg = configs.get_smoke_config(ARCH)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    eng = Engine(params, cfg, n_slots=2, max_seq=64,
                 sched=SchedulerConfig(prefill_chunk=8, decode_steps=2),
                 page_size=8, prefix_cache=False)
    return cfg, eng


def _prompt(cfg, n, seed=0):
    return np.random.RandomState(seed).randint(0, cfg.vocab_size, n).tolist()


def _host_events(path):
    """name -> list of stats dicts of every host-plane event whose name
    starts with ``repro.``."""
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    assert files, f"no .xplane.pb under {path}"
    out = {}
    for plane in ProfileData.from_file(files[0]).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    out.setdefault(ev.name, []).append(dict(ev.stats))
    return out


def test_profile_holds_program_annotations(setup, tmp_path):
    cfg, eng = setup
    svc = Service(eng, ServiceConfig(queue_depth=4))
    door = HttpFrontDoor(svc, host="127.0.0.1", port=0)

    async def one_pump_iteration():
        await door.start()
        assert await door._ask(("idle", door._loop.create_future()))
        await asyncio.wait_for(door.stop(drain=True), timeout=60)

    svc.submit(Request(prompt=_prompt(cfg, 10), max_new_tokens=3))
    svc.step()                      # compiles outside the traced stretch
    jax.profiler.start_trace(str(tmp_path))
    try:
        while svc.has_work:
            svc.step()
        asyncio.run(one_pump_iteration())
    finally:
        jax.profiler.stop_trace()

    evs = _host_events(str(tmp_path))
    want = {"repro.engine.step", "repro.engine.admit",
            "repro.engine.prefill_dispatch", "repro.engine.decode_scan",
            "repro.engine.host_sync", "repro.engine.token_fanout",
            "repro.service.step", "repro.service.expire",
            "repro.door.inbox", "repro.door.idle", "repro.door.flush"}
    assert want <= set(evs), sorted(want - set(evs))
    for name, stats in evs.items():
        for st in stats:
            assert float(st["t_engine"]) > 0, (name, st)
    assert all("step_num" in st for st in evs["repro.engine.step"])
    assert all({"lo", "hi", "window"} <= set(st)
               for st in evs["repro.engine.prefill_dispatch"])
    assert all({"live", "window"} <= set(st)
               for st in evs["repro.engine.decode_scan"])
    for st in evs["repro.door.flush"]:
        assert float(st["t_engine"]) >= float(st["t_staged"])


def test_annotation_tags_the_reading_taken_as_it_opened(tmp_path):
    """``Annotation`` reads its clock once, after the annotation opened,
    and the profile carries that reading as ``t_engine`` beside the
    block's own arguments."""
    from repro.serving.engine import Annotation
    reads = []

    def clk():
        reads.append(1.25 * (len(reads) + 1))
        return reads[-1]

    jax.profiler.start_trace(str(tmp_path))
    try:
        with Annotation("repro.test.block", clk, lo=3) as a:
            assert reads == [1.25] and a.t0 == 1.25
        with Annotation("repro.test.step", clk, step_num=7):
            pass
    finally:
        jax.profiler.stop_trace()
    assert len(reads) == 2
    evs = _host_events(str(tmp_path))
    [block] = evs["repro.test.block"]
    assert float(block["t_engine"]) == 1.25 and int(block["lo"]) == 3
    [step] = evs["repro.test.step"]
    assert float(step["t_engine"]) == 2.5 and int(step["step_num"]) == 7


def _ticking_clock(dt=1e-4):
    now = [0.0]

    def clk():
        now[0] += dt
        return now[0]
    return clk


def _phases_of_a_run(cfg, eng, profile_dir=None):
    """Drive the same requests on a fresh deterministic clock; the
    ``phases`` dict of every tick."""
    svc = Service(eng, ServiceConfig(queue_depth=4), clock=_ticking_clock())
    for i, n in enumerate((12, 5)):
        svc.submit(Request(prompt=_prompt(cfg, n, seed=i),
                           max_new_tokens=4))
    out = []
    if profile_dir is not None:
        jax.profiler.start_trace(profile_dir)
    try:
        while svc.has_work:
            svc.step()
            out.append(dict(eng.last_step["phases"]))
    finally:
        if profile_dir is not None:
            jax.profiler.stop_trace()
    return out


def test_phases_keep_keys_and_sums_with_and_without_a_session(setup,
                                                            tmp_path):
    cfg, eng = setup
    _phases_of_a_run(cfg, eng)      # warm every shape of the run
    plain = _phases_of_a_run(cfg, eng)
    traced = _phases_of_a_run(cfg, eng, str(tmp_path))
    assert plain == traced
    tail = {"admit", "prefill_dispatch", "host_sync", "token_fanout",
            "total"}
    kinds = [frozenset(p) for p in plain]
    assert set(kinds) == {frozenset(tail),
                          frozenset({"admit", "prefill_dispatch", "total"}),
                          frozenset({"admit", "decode_scan", "host_sync",
                                     "token_fanout", "total"})}
    for p in plain:
        assert set(p) <= set(schema.PHASES)
        parts = sum(v for k, v in p.items() if k != "total")
        assert 0 < parts <= p["total"] + 1e-12


def _scopes(fn, args):
    txt = fn.lower(*args).as_text(dialect="hlo", debug_info=True)
    return {seg for line in txt.splitlines() if "op_name=" in line
            for seg in line.split('op_name="', 1)[1].split('"', 1)[0]
            .split("/")}


@pytest.mark.parametrize("step,want", [
    ("engine.prefill", {"kv_gather", "kv_scatter", "kv_write", "attention",
                        "mlp", "logits"}),
    ("engine.decode", {"kv_select", "kv_write", "attention", "mlp",
                       "logits", "sample"}),
    ("engine.reset", {"kv_reset"})])
def test_step_hlo_carries_scopes(setup, step, want):
    _, eng = setup
    fn, args = engine_hot_paths(eng)[step]
    assert want <= _scopes(fn, args)


def test_executables_are_named(setup):
    _, eng = setup
    names = {"engine.reset": "engine_reset",
             "engine.prefill": "engine_prefill",
             "engine.decode": "engine_decode",
             "engine.copy_page": "engine_copy_page"}
    for key, (fn, args) in engine_hot_paths(eng).items():
        assert fn.__name__ == names[key]
        head = fn.lower(*args).as_text(dialect="hlo").splitlines()[0]
        assert head.startswith(f"HloModule jit_{names[key]},"), head
