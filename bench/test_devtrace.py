"""Self-test of the trace reduction, on a trace recorded on the chip.

    python -m pytest -q bench/test_devtrace.py

``testdata/trace_v5e.json`` is ``devtrace.extract`` of a ``--trace 1`` run
of ``hqp-chat`` on one TPU v5e, cut to its first part; it was recorded
before a record carried its kernel classes, and is given ``KERNELS``, as
``extract`` gives a cell whose file lists none.
"""
import json
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent))

from bench import devtrace  # noqa: E402


@pytest.fixture(scope="module")
def rec():
    return dict(json.loads((BENCH / "testdata" / "trace_v5e.json")
                           .read_text()),
                kernels=devtrace.kernel_classes())


def sweep_union(intervals):
    """Covered length by counting open intervals at each boundary: another
    algorithm than ``devtrace.union``'s merge."""
    edges = sorted([(a, 1) for a, b in intervals] +
                   [(b, -1) for a, b in intervals])
    total, depth, last = 0.0, 0, None
    for t, d in edges:
        if depth > 0:
            total += t - last
        depth += d
        last = t
    return total


@pytest.mark.parametrize("ivs,want", [
    ([], 0.0), ([(0, 1)], 1.0), ([(0, 2), (1, 3)], 3.0),
    ([(0, 1), (2, 3)], 2.0), ([(0, 5), (1, 2), (3, 4)], 5.0),
    ([(1, 2), (0, 1)], 2.0)])
def test_union(ivs, want):
    assert devtrace.union(ivs) == pytest.approx(want)
    assert sweep_union(ivs) == pytest.approx(want)


def test_recorded_trace_is_a_device_trace(rec):
    assert {op["device"] for op in rec["ops"]} == {"/device:TPU:0"}
    assert rec["window_s"] > 0 and len(rec["ops"]) > 100


def test_busy_matches_independent_union(rec):
    ivs = [(a, b) for a, b, _ in devtrace.clip(rec)]
    assert devtrace.busy_s(rec) == pytest.approx(sweep_union(ivs))
    assert 0 < devtrace.busy_s(rec) <= rec["window_s"]


def test_idle_gaps_and_busy_fill_the_window(rec):
    gaps = devtrace.idle_gaps(rec, n=10 ** 9)
    total = sum(g for _, g in gaps)
    assert total + devtrace.busy_s(rec) == pytest.approx(rec["window_s"])
    longest = devtrace.idle_gaps(rec)
    assert len(longest) <= 10
    assert [g for _, g in longest] == sorted((g for _, g in longest),
                                            reverse=True)


def test_kernels_are_found_by_name_stack(rec):
    secs = {k: devtrace.kernel_seconds(rec, k)
            for k in ("attention", "w8a8_matmul", "w8a8_quantize")}
    assert all(v > 0 for v in secs.values()), secs
    assert sum(secs.values()) <= devtrace.busy_s(rec) * (1 + 1e-9)


def test_top_ops_are_the_largest(rec):
    top = devtrace.top_ops(rec)
    assert len(top) <= 10
    assert [v for _, v in top] == sorted((v for _, v in top), reverse=True)
    assert sum(v for _, v in top) <= sum(b - a for a, b, _ in
                                         devtrace.clip(rec)) * (1 + 1e-9)


# what KERNELS' classes read on this trace before a configuration could
# add classes of its own
TODAY = {"attention": 0.022688699000000003,
         "w8a8_matmul": 0.0027781759999998216,
         "w8a8_quantize": 5.1324999999874735e-05}


def test_todays_classes_read_the_same_seconds(rec):
    assert {k: devtrace.kernel_seconds(rec, k) for k in TODAY} == TODAY


def test_a_configuration_files_class_classes_its_operations(rec):
    """``tiny-jamba.json`` declares ``selective_scan``; the trace's plain
    ``fusion`` operations, renamed to its prefix, are classed by it where
    the record carries the file's classes (as ``extract`` makes it), and
    by nothing where it carries ``KERNELS`` alone."""
    cfg = json.loads((BENCH / "testdata" / "tiny-jamba.json").read_text())
    (cls, prefix), = cfg["kernels"]
    plain = lambda op: op["name"].split(".")[0] == "fusion"  # noqa: E731
    fusion_s = sum(b - a for a, b, op in devtrace.clip(rec) if plain(op))
    ops = [dict(op, name=f"{prefix}.{i}") if plain(op) else op
           for i, op in enumerate(rec["ops"])]
    mine = dict(rec, ops=ops,
                kernels=devtrace.kernel_classes(cfg["kernels"]))
    assert fusion_s > 0
    assert devtrace.kernel_seconds(mine, cls) == pytest.approx(fusion_s)
    assert devtrace.kernel_seconds(dict(rec, ops=ops), cls) == 0
    assert {k: devtrace.kernel_seconds(mine, k) for k in TODAY} == TODAY
    top = dict(devtrace.top_ops(mine))
    assert top[cls] == pytest.approx(fusion_s) and "fusion" not in top
