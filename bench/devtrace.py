"""From a profiler trace to the numbers the per-layer metrics read.

``capture`` wraps a stretch of the measured window in ``jax.profiler``;
``extract`` reads the ``.xplane.pb`` it wrote into a small JSON-able record
(device operations with their names and intervals, the benchmark's host
annotations, the traced window); the functions below reduce such a
record.
The self-test ``bench/test_devtrace.py`` checks the reduction on a trace
recorded on the chip and kept in ``bench/testdata``.

Device operations are told apart by the name XLA gives each operation
in the trace, which for a Pallas call is the jitted function the program
wraps it in (``paged_prefill_attention_pallas.12`` for cache attention in
prefill and decode, ``int8_matmul_pallas``, ``quantize_rowwise_pallas``):
the three kernels themselves are all named ``_kernel``. A configuration
file may list more classes as ``kernels``, ``[class, name prefix]`` pairs;
``extract`` keeps a trace's classes in its record, and every reduction
below classes the trace's operations by them. The events of control-flow
operations (``while`` and the like) enclose their bodies' operations and
are left out, so that no time counts twice.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

HOST_PREFIX = "bench."
# kernel classes by the name XLA gives the operation, first match wins
KERNELS = (("attention", "paged_prefill_attention_pallas"),
           ("attention", "prefill_attention_pallas"),
           ("w8a8_matmul", "int8_matmul_pallas"),
           ("w8a8_quantize", "quantize_rowwise_pallas"))
# control-flow operations whose events enclose their bodies' operations
CONTAINERS = ("while", "conditional", "call")


class capture:
    """Context manager: ``jax.profiler`` trace into ``path``."""

    def __init__(self, path: str):
        self.path = path

    def __enter__(self):
        import jax
        jax.profiler.start_trace(self.path)
        return self

    def __exit__(self, *exc):
        import jax
        jax.profiler.stop_trace()
        return False


def extract(path: str, window: Tuple[float, float],
            kernels: Sequence[Sequence[str]] = ()) -> dict:
    """Read the newest ``.xplane.pb`` under ``path``. ``window`` is the
    traced stretch on the host's monotonic clock, (start, end); the host
    annotation ``bench.window`` marks the same stretch on the trace's
    clock, and every time in the record is relative to its start, in
    seconds. ``kernels``: the configuration's classes, after
    ``KERNELS``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    pd = ProfileData.from_file(files[-1])
    host, ops = [], []
    for plane in pd.planes:
        is_dev = plane.name.startswith("/device:")
        for line in plane.lines:
            evs = list(line.events)
            if is_dev and line.name == "XLA Ops":
                for ev in evs:
                    name = ev.name.split(" = ", 1)[0].lstrip("%")
                    if name.split(".")[0] in CONTAINERS:
                        continue
                    ops.append({"device": plane.name, "name": name,
                                "t": ev.start_ns * 1e-9,
                                "dur": ev.duration_ns * 1e-9})
            elif not is_dev:
                for ev in evs:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append({"name": ev.name,
                                     "t": ev.start_ns * 1e-9,
                                     "dur": ev.duration_ns * 1e-9})
    marks = [h for h in host if h["name"] == HOST_PREFIX + "window"]
    if not marks:
        raise ValueError("trace holds no bench.window annotation")
    t0 = marks[0]["t"]
    span = marks[0]["dur"]
    for rec in ops + host:
        rec["t"] -= t0
    host = [h for h in host if h["name"] != HOST_PREFIX + "window"]
    return {"window_s": span, "host_t0": window[0], "ops": ops,
            "host": host, "kernels": kernel_classes(kernels)}


def clip(rec: dict) -> List[Tuple[float, float, dict]]:
    """Device operations as (start, end) clipped to the window."""
    out = []
    for op in rec["ops"]:
        a, b = max(0.0, op["t"]), min(rec["window_s"], op["t"] + op["dur"])
        if b > a:
            out.append((a, b, op))
    return out


def union(intervals: Sequence[Tuple[float, float]]) -> float:
    """Total length covered by a set of intervals."""
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def busy_s(rec: dict) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    by_dev: Dict[str, list] = {}
    for a, b, op in clip(rec):
        by_dev.setdefault(op["device"], []).append((a, b))
    if not by_dev:
        return 0.0
    return sum(union(v) for v in by_dev.values()) / len(by_dev)


def kernel_classes(extra: Sequence[Sequence[str]] = ()) -> List[list]:
    """``KERNELS`` and then a configuration's ``[class, prefix]`` pairs."""
    return [list(k) for k in KERNELS + tuple(map(tuple, extra))]


def kernel_of(op: dict, kernels: Sequence[Sequence[str]]) -> Optional[str]:
    for cls, needle in kernels:
        if op["name"].startswith(needle):
            return cls
    return None


def kernel_seconds(rec: dict, cls: str) -> float:
    """Summed device time of one kernel class inside the window."""
    return sum(b - a for a, b, op in clip(rec)
               if kernel_of(op, rec["kernels"]) == cls)


def top_ops(rec: dict, n: int = 10) -> List[list]:
    """The device operations that took most time, by kernel class where
    one applies and by the kind of operation (``fusion``, ``copy``...)
    otherwise."""
    acc: Dict[str, float] = {}
    for a, b, op in clip(rec):
        key = kernel_of(op, rec["kernels"]) or op["name"].split(".")[0]
        acc[key] = acc.get(key, 0.0) + (b - a)
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(rec: dict, n: int = 10) -> List[list]:
    """The longest stretches with no device operation, each named by the
    host annotation open at its middle (``host idle`` where none is)."""
    ivs = sorted((a, b) for a, b, _ in clip(rec))
    gaps, end = [], 0.0
    for a, b in ivs:
        if a > end:
            gaps.append((end, a))
        end = max(end, b)
    if rec["window_s"] > end:
        gaps.append((end, rec["window_s"]))
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (a + b) / 2
        open_ = [h["name"][len(HOST_PREFIX):] for h in rec["host"]
                 if h["t"] <= mid <= h["t"] + h["dur"]]
        out.append(["+".join(sorted(set(open_))) or "host idle", b - a])
    return out
