"""From the program's own names in a profiler trace to the numbers of four
per-layer metrics.

The program names its work in any profile it runs under (docs/SERVING.md,
"Profiles"): host annotations ``repro.engine.*`` (an engine tick and its
phases), ``repro.service.*`` and ``repro.door.*`` (the front door's pump
and event loop), each with ``t_engine``, the engine-clock reading taken as
it opened; executables ``jit_engine_prefill``, ``jit_engine_decode``...;
and ``jax.named_scope`` scopes (``kv_*``, ``attention``, ``mlp``,
``logits``, ``sample``) in each device operation's HLO metadata.

``extract`` reads the ``.xplane.pb`` that ``devtrace.capture`` wrote into a
small JSON-able record: the stretch (``bench.window``), the ``repro.*``
annotations with their thread and arguments, and the device operations,
each with the executable that ran it, its innermost program scope and
whether it moves the executable's in-place state (from the HLO module
protos the trace holds). The functions below reduce such a record;
``from_run`` is what the metric readers call. ``bench/test_progtrace.py``
checks them on synthetic records, on profiles taken where it runs and on
profiles recorded on the chip.

A program that does not name its work (an older tree) leaves a trace with
no ``repro.*`` annotation: ``extract`` then returns None, and so does every
reader.
"""
from __future__ import annotations

import bisect
import glob
import os
import pathlib
import re
import sys
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from bench import devtrace, stats

PREFIX = "repro."
WINDOW = devtrace.HOST_PREFIX + "window"
TRACE_DIR = pathlib.Path(__file__).resolve().parent / ".out" / "trace"
IDLE = "repro.door.idle"
FLUSH = "repro.door.flush"
PREFILL_MODULES = ("jit_engine_prefill", "jit_engine_spec_prefill")
# the program's scopes, innermost wins (``in_pool``: every kv_* scope is
# KV pool work)
SCOPES = ("kv_gather", "kv_scatter", "kv_select", "kv_reset", "kv_write",
          "attention", "mlp", "logits", "sample")
# operations that move the KV pool's bytes rather than compute on them,
# by the kind XLA names them with
MOVES = ("copy", "dynamic-slice", "dynamic-update-slice")


# ------------------------------------------------------------ extraction
def _varint(b, i: int) -> Tuple[int, int]:
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        if c < 0x80:
            return r, i
        s += 7


def _fields(b) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one protobuf message, in wire order;
    length-delimited values as memoryview slices (not copied)."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        f, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 1:
            v, i = b[i:i + 8], i + 8
        elif wire == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wire == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield f, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def innermost_scope(op_name: str) -> Optional[str]:
    """The last of the program's scopes on an HLO ``op_name`` path."""
    hit = [seg for seg in op_name.split("/") if seg in SCOPES]
    return hit[-1] if hit else None


def _ints(vals) -> List[int]:
    """A repeated integer field, packed or not."""
    out: List[int] = []
    for v in vals:
        if isinstance(v, int):
            out.append(v)
        else:
            i, b = 0, bytes(v)
            while i < len(b):
                x, i = _varint(b, i)
                out.append(x)
    return out


def _shapes(blob) -> List[Tuple[int, Tuple[int, ...]]]:
    """(element type, dimensions) of a ShapeProto, one per array of a
    tuple. ShapeProto.element_type = 2, .dimensions = 3, .tuple_shapes =
    4."""
    dims, et, parts = [], 0, []
    for f, v in _fields(blob):
        if f == 2:
            et = v
        elif f == 3:
            dims.append(v)
        elif f == 4:
            parts.extend(_shapes(v))
    return parts if parts else [(et, tuple(_ints(dims)))]


def _squeeze(dims: Tuple[int, ...]) -> Tuple[int, ...]:
    while dims and dims[0] == 1:
        dims = dims[1:]
    return dims


def hlo_programs(raw: bytes) -> Dict[str, Dict[str, Tuple[Optional[str],
                                                            bool]]]:
    """program id (as text) -> {instruction name: (innermost program
    scope, whether it moves the executable's state)}, from the
    ``HloProto`` of each executable the trace's ``/host:metadata`` plane
    holds: an event metadata per program (its id) with a bytes stat named
    ``Hlo Proto``. Wire fields: XSpace.planes = 1; XPlane.name = 2,
    .event_metadata = 4, .stat_metadata = 5 (maps: key = 1, value = 2);
    XEventMetadata.id = 1, .stats = 5; XStatMetadata.id = 1, .name = 2;
    XStat.metadata_id = 1, .bytes_value = 6; HloProto.hlo_module = 1."""
    out: Dict[str, Dict[str, Tuple[Optional[str], bool]]] = {}
    for f, plane in _fields(memoryview(raw)):
        if f != 1:
            continue
        fields = list(_fields(plane))
        if _text(dict(fields).get(2, b"")) != "/host:metadata":
            continue
        stat_names = {}
        for g, v in fields:
            if g == 5:
                sm = dict(_fields(dict(_fields(v)).get(2, b"")))
                stat_names[sm.get(1)] = _text(sm.get(2, b""))
        for g, v in fields:
            if g != 4:
                continue
            em = list(_fields(dict(_fields(v)).get(2, b"")))
            pid = dict(em).get(1)
            for h, stat in em:
                st = dict(_fields(stat)) if h == 5 else {}
                if stat_names.get(st.get(1)) == "Hlo Proto" and 6 in st:
                    out[str(pid)] = _hlo_module_ops(
                        dict(_fields(st[6])).get(1, b""))
    return out


def _hlo_module_ops(module) -> Dict[str, Tuple[Optional[str], bool]]:
    """{instruction: (innermost program scope, moves the state)} of one
    HloModuleProto. The state is what the executable updates in place:
    the entry parameters that ``input_output_alias`` pairs with an output
    (the engine's donated KV pool; its arrays of one dimension, such as
    the slots' positions, aside). An instruction moves it where its kind
    is a move (``is_move``) and an array it yields has the element type
    and shape of a state array or of one layer's slice of one (leading
    dimension gone), leading 1s aside. Wire fields: HloModuleProto.name =
    1, .computations = 3, .entry_computation_id = 6, .input_output_alias =
    8; HloInputOutputAliasProto.entries = 1;
    AliasEntryProto.parameter_number = 2; HloComputationProto.instructions
    = 2, .id = 5; HloInstructionProto.name = 1, .opcode = 2, .shape = 3,
    .metadata = 7, .parameter_number = 9; OpMetadata.op_name = 2."""
    mod = list(_fields(module))
    entry = dict(mod).get(6, 0)
    aliased = {dict(_fields(e)).get(2, 0)
               for k, a in mod if k == 8
               for kk, e in _fields(a) if kk == 1}
    params: Dict[int, list] = {}
    ins: List[Tuple[str, Optional[str], list]] = []
    for k, comp in mod:
        if k != 3:
            continue
        cf = list(_fields(comp))
        is_entry = dict(cf).get(5, 0) == entry
        for kk, blob in cf:
            if kk != 2:
                continue
            fields = dict(_fields(blob))
            meta = fields.get(7)
            opn = dict(_fields(meta)).get(2) if meta else None
            shapes = _shapes(fields[3]) if 3 in fields else []
            name = _text(fields.get(1, b""))
            if is_entry and _text(fields.get(2, b"")) == "parameter":
                params[fields.get(9, 0)] = shapes
            ins.append((name, innermost_scope(_text(opn)) if opn else None,
                        shapes))
    state = {(et, _squeeze(d)) for p in aliased for et, dims in
             params.get(p, []) if len(dims) > 1 for d in (dims, dims[1:])}
    return {name: (scope, is_move(name) and any(
        (et, _squeeze(d)) in state for et, d in shapes))
        for name, scope, shapes in ins}


def _module(name: str) -> Tuple[str, str]:
    """``jit_engine_decode(6761123569262186470)`` -> (module name, program
    id), as the device plane's ``XLA Modules`` line names an executable."""
    m = re.fullmatch(r"(.*?)\((\d+)\)", name.strip())
    return (m.group(1), m.group(2)) if m else (name.strip(), "")


def _op_name(name: str) -> str:
    return name.split(" = ", 1)[0].lstrip("%")


def _newest(path: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return files[-1] if files else None


def extract(path: str) -> Optional[dict]:
    """Read the newest ``.xplane.pb`` under ``path`` (a directory) or the
    file ``path``. Returns None where the trace holds no ``repro.*``
    annotation. Times are seconds from the start of the ``bench.window``
    annotation (from the first annotation where there is none); the
    stretch is ``window_s`` long."""
    from jax.profiler import ProfileData
    f = path if os.path.isfile(path) else _newest(path)
    if f is None:
        return None
    raw = pathlib.Path(f).read_bytes()
    pd = ProfileData.from_serialized_xspace(raw)
    ann, window, modules, ops = [], None, [], []
    for p, plane in enumerate(pd.planes):
        is_dev = plane.name.startswith("/device:")
        for ln, line in enumerate(plane.lines):
            if not is_dev:
                for ev in line.events:
                    if ev.name == WINDOW and window is None:
                        window = (ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                    elif ev.name.startswith(PREFIX):
                        ann.append({"name": ev.name, "thread": f"{p}.{ln}",
                                    "t": ev.start_ns * 1e-9,
                                    "dur": ev.duration_ns * 1e-9,
                                    "args": {k: str(v) for k, v in
                                             ev.stats}})
            elif line.name == "XLA Modules":
                for ev in line.events:
                    name, prog = _module(ev.name)
                    modules.append({"device": plane.name, "name": name,
                                    "program": prog,
                                    "t": ev.start_ns * 1e-9,
                                    "dur": ev.duration_ns * 1e-9})
            elif line.name == "XLA Ops":
                for ev in line.events:
                    name = _op_name(ev.name)
                    if name.split(".")[0] in devtrace.CONTAINERS:
                        continue
                    ops.append({"device": plane.name, "name": name,
                                "t": ev.start_ns * 1e-9,
                                "dur": ev.duration_ns * 1e-9})
    if not ann:
        return None
    _attribute(ops, modules, hlo_programs(raw))
    t0, span = window if window else (
        min(a["t"] for a in ann),
        max(a["t"] + a["dur"] for a in ann) - min(a["t"] for a in ann))
    for rec in ann + ops:
        rec["t"] -= t0
    return {"window_s": span, "ann": ann, "ops": ops}


def _attribute(ops: List[dict], modules: List[dict],
               programs: Dict[str, Dict[str, Tuple[Optional[str], bool]]]
               ) -> None:
    """Give each operation the module whose event encloses its start on
    its device (``""`` where none does), and its innermost program scope
    and whether it moves the module's state (``pool``), looked up in that
    module's program."""
    by_dev: Dict[str, Tuple[List[float], List[dict]]] = {}
    for m in sorted(modules, key=lambda m: m["t"]):
        starts, mods = by_dev.setdefault(m["device"], ([], []))
        starts.append(m["t"])
        mods.append(m)
    for op in ops:
        starts, mods = by_dev.get(op["device"], ([], []))
        i = bisect.bisect_right(starts, op["t"]) - 1
        m = (mods[i] if i >= 0 and op["t"] <= mods[i]["t"] + mods[i]["dur"]
             else {"name": "", "program": ""})
        op["module"] = m["name"]
        op["scope"], op["pool"] = programs.get(m["program"], {}).get(
            op["name"], (None, False))


# ------------------------------------------------------------ reductions
def busy_s(rec: dict, keep=lambda op: True) -> float:
    """``devtrace.busy_s`` of the operations ``keep`` selects."""
    return devtrace.busy_s(dict(rec, ops=[op for op in rec["ops"]
                                          if keep(op)]))


def idle_intervals(rec: dict) -> List[Tuple[float, float]]:
    """Stretches of the window in which no operation runs on the device
    (as ``devtrace.idle_gaps`` finds them)."""
    gaps, end = [], 0.0
    for a, b in sorted((a, b) for a, b, _ in devtrace.clip(rec)):
        if a > end:
            gaps.append((end, a))
        end = max(end, b)
    if rec["window_s"] > end:
        gaps.append((end, rec["window_s"]))
    return gaps


def _overlap(ivs: Sequence[Tuple[float, float]],
             cover: Sequence[Tuple[float, float]]) -> float:
    """Length of ``ivs`` (disjoint) that ``cover`` covers."""
    merged, end = [], float("-inf")
    for a, b in sorted(cover):
        if merged and a <= end:
            end = max(end, b)
            merged[-1] = (merged[-1][0], end)
        else:
            merged.append((a, b))
            end = b
    total, j = 0.0, 0
    for a, b in sorted(ivs):
        while j < len(merged) and merged[j][1] <= a:
            j += 1
        k = j
        while k < len(merged) and merged[k][0] < b:
            total += min(b, merged[k][1]) - max(a, merged[k][0])
            k += 1
    return total


def prefill_device_share(rec: dict) -> Optional[float]:
    """% of device busy time spent in the prefill executables."""
    busy = busy_s(rec)
    if not busy or not any(op["module"] in PREFILL_MODULES
                           for op in rec["ops"]):
        return None
    return 100.0 * busy_s(rec, lambda op: op["module"] in PREFILL_MODULES) \
        / busy


def in_pool(op: dict) -> bool:
    """An operation of the KV state pool: under a ``kv_*`` scope, or a move
    of the executable's in-place state (the arena sliced, written back or
    copied, wherever the program or XLA put it)."""
    return bool(op.get("pool")) or (op.get("scope") or "").startswith("kv_")


def kv_pool_share(rec: dict) -> Optional[float]:
    """% of device busy time spent in the KV state pool's operations
    (``in_pool``)."""
    busy = busy_s(rec)
    if not busy or not any(in_pool(op) for op in rec["ops"]):
        return None
    return 100.0 * busy_s(rec, in_pool) / busy


def host_bound_idle_share(rec: dict) -> Optional[float]:
    """% of the stretch in which the device idles while the pump is not
    parked for want of work (outside every ``repro.door.idle``)."""
    if not rec["ops"] or not any(a["name"].startswith("repro.door.")
                                 for a in rec["ann"]):
        return None
    gaps = idle_intervals(rec)
    parked = [(a["t"], a["t"] + a["dur"]) for a in rec["ann"]
              if a["name"] == IDLE]
    idle = sum(b - a for a, b in gaps)
    return 100.0 * (idle - _overlap(gaps, parked)) / rec["window_s"]


def flush_delays_s(rec: dict) -> List[float]:
    """``t_engine - t_staged`` of every ``repro.door.flush`` that opened in
    the stretch: how long a pump iteration's tokens waited for the loop."""
    return [float(a["args"]["t_engine"]) - float(a["args"]["t_staged"])
            for a in rec["ann"] if a["name"] == FLUSH
            and 0.0 <= a["t"] < rec["window_s"]]


def flush_delay_p95_ms(rec: dict) -> Optional[float]:
    v = stats.nearest_rank(flush_delays_s(rec), 95)
    return None if v is None else 1e3 * v


def clock_fit(rec: dict) -> Optional[Tuple[float, float, int]]:
    """One offset, trace clock minus engine clock, fitted (least squares)
    over every annotation's start and its ``t_engine``; returns (offset s,
    largest residual s, annotations). With it a time on the engine's
    clock (a ``SpanRecorder`` record) lands on the trace at
    ``t_engine + offset``."""
    d = [a["t"] - float(a["args"]["t_engine"]) for a in rec["ann"]
         if "t_engine" in a["args"]]
    if not d:
        return None
    off = sum(d) / len(d)
    return off, max(abs(x - off) for x in d), len(d)


def _depths(ann: Sequence[dict]) -> List[int]:
    """How many annotations of its own thread enclose each annotation."""
    depth = [0] * len(ann)
    by_thread: Dict[str, List[int]] = {}
    for i, a in enumerate(ann):
        by_thread.setdefault(a["thread"], []).append(i)
    end = lambda i: ann[i]["t"] + ann[i]["dur"]
    for idx in by_thread.values():
        idx.sort(key=lambda i: (ann[i]["t"], -ann[i]["dur"]))
        stack: List[int] = []
        for i in idx:
            while stack and end(stack[-1]) < end(i):
                stack.pop()
            depth[i] = len(stack)
            stack.append(i)
    return depth


def idle_by_annotation(rec: dict) -> Dict[str, float]:
    """Device-idle seconds by the innermost ``repro.*`` annotation open
    (deepest nesting on its thread, then latest start); ``none`` where no
    annotation is open."""
    ann, w = rec["ann"], rec["window_s"]
    depth = _depths(ann)
    edges = []
    for i, a in enumerate(ann):
        edges.append((min(max(a["t"], 0.0), w), 1, i))
        edges.append((min(max(a["t"] + a["dur"], 0.0), w), -1, i))
    for a, b in idle_intervals(rec):
        edges.append((a, 1, -1))
        edges.append((b, -1, -1))
    edges.sort(key=lambda e: e[0])
    out: Dict[str, float] = {}
    open_: set = set()
    idle, last = 0, 0.0
    for t, step, i in edges:
        if idle and t > last:
            key = (max((depth[j], ann[j]["t"], ann[j]["name"])
                       for j in open_)[2] if open_ else "none")
            out[key] = out.get(key, 0.0) + (t - last)
        last = t
        if i < 0:
            idle += step
        elif step > 0:
            open_.add(i)
        else:
            open_.discard(i)
    return out


def moves_by(rec: dict) -> Tuple[Dict[str, float], Dict[str, float]]:
    """Device seconds of the copy / dynamic-slice / dynamic-update-slice
    operations (their own names or fusions named after them), by module
    and by innermost program scope (``unscoped`` where none)."""
    by_mod: Dict[str, float] = {}
    by_scope: Dict[str, float] = {}
    for a, b, op in devtrace.clip(rec):
        if not is_move(op["name"]):
            continue
        m = op["module"] or "unknown"
        s = op.get("scope") or "unscoped"
        by_mod[m] = by_mod.get(m, 0.0) + (b - a)
        by_scope[s] = by_scope.get(s, 0.0) + (b - a)
    return by_mod, by_scope


def is_move(name: str) -> bool:
    """``copy.3``, ``copy-start``, ``constant_dynamic-slice_fusion.1``..."""
    return any(part in MOVES or part.startswith("copy-")
               for part in name.split(".")[0].split("_"))


def summary_lines(rec: dict) -> List[str]:
    """The traced run's two attribution lines and the clock fit."""
    busy = busy_s(rec)
    named = busy_s(rec, lambda op: bool(op["module"]))
    idle = idle_by_annotation(rec)
    total_idle = sum(idle.values())
    under = total_idle - idle.get("none", 0.0)
    by_mod, by_scope = moves_by(rec)
    pool = busy_s(rec, in_pool)
    state = busy_s(rec, lambda op: bool(op.get("pool")))
    fit = clock_fit(rec)
    fmt = lambda d: " ".join(f"{k}={v:.6f}s" for k, v in
                             sorted(d.items(), key=lambda kv: -kv[1]))
    return [
        f"[progtrace] idle {total_idle:.6f}s of {rec['window_s']:.6f}s, "
        f"{100 * under / total_idle if total_idle else 0:.2f}% under a "
        f"repro.* annotation, by innermost: {fmt(idle)}",
        f"[progtrace] moves {sum(by_mod.values()):.6f}s of busy "
        f"{busy:.6f}s ({100 * named / busy if busy else 0:.2f}% of busy "
        f"time put to a module); by module: {fmt(by_mod)}; by scope: "
        f"{fmt(by_scope)}; KV pool {pool:.6f}s, of it moves of the "
        f"executables' state {state:.6f}s",
        "[progtrace] clock fit " + (
            "none" if fit is None else
            f"offset {fit[0]:.9f}s, largest residual "
            f"{1e6 * fit[1]:.3f}us over {fit[2]} annotations")]


_CACHE: Dict[tuple, Optional[dict]] = {}


def from_run(rec: dict) -> Optional[dict]:
    """The record of the traced run's profile, which ``bench/run.py``
    writes under ``bench/.out/trace``, read once per file and logged
    (three lines on standard error) when first read; None where the run
    took no trace or the program names nothing in it."""
    if rec.get("trace") is None:
        return None
    f = _newest(str(TRACE_DIR))
    if f is None:
        return None
    key = (f, os.path.getmtime(f))
    if key not in _CACHE:
        try:
            pt = extract(f)
            lines = [] if pt is None else summary_lines(pt)
        except Exception as e:                          # noqa: BLE001
            # a reader must not fail the run it measures: say why, give
            # no number
            pt, lines = None, [f"[progtrace] unreadable trace {f}: "
                               f"{type(e).__name__}: {e}"]
        _CACHE[key] = pt
        for line in lines:
            print(line, file=sys.stderr, flush=True)
    return _CACHE[key]
