"""The work a served window needs, counted from the engine's spans.

A roofline share or a utilisation counts the operations and bytes the
algorithm needs for the tokens actually processed, never what an
implementation happens to do: attention over each request's real context
length (not the bucketed window, padded query rows or block sizes), matrix
products over the live rows of a step (not the idle slots of the batch),
logits only where a token is produced. So the count stays the same
whatever implements a kernel.

The model is a tuple of layers, each with its own cache attention (or
none), its own linears and, for work that neither expresses (a recurrent
scan, say), extra calls of a kernel class. A configuration's reference
module builds it (``work_model``), from the served widths.

The engine's spans (``telemetry.SpanRecorder``) say what each dispatch
processed: a ``prefill`` span covers prompt positions [lo, hi) of one
request; the ``decode`` spans of one dispatch share its start and end, and
each says how many tokens its request emitted in the dispatch's scan.

Calls are kept by kernel class (``devtrace.kernel_of``'s names): one call
of a class per dispatch step, summed over the layers that make it.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Dict, List, Optional, Tuple

ATTENTION = "attention"
W8A8_MATMUL = "w8a8_matmul"
W8A8_QUANTIZE = "w8a8_quantize"


@dataclasses.dataclass(frozen=True)
class Attention:
    """Cache attention of one layer."""
    heads: int
    kv_heads: int
    head_dim: int
    kv_bytes: int                   # bytes per cached K or V element
    kv_scale_bytes: int             # bytes of scales per (position, head)


@dataclasses.dataclass(frozen=True)
class Linear:
    """One linear (K, N), on every live row of a step."""
    k: int
    n: int


@dataclasses.dataclass(frozen=True)
class Extra:
    """Work of one kernel class that no linear or attention expresses, in
    one layer: operations and bytes per token, and bytes per row (state a
    request reads and writes once a call). ``precision`` names the peak of
    ``peaks.json`` its roofline is taken at."""
    kernel: str
    precision: str
    ops_per_token: float = 0.0
    bytes_per_token: float = 0.0
    bytes_per_row: float = 0.0


@dataclasses.dataclass(frozen=True)
class Layer:
    attention: Optional[Attention] = None
    linears: Tuple[Linear, ...] = ()
    extra: Tuple[Extra, ...] = ()


@dataclasses.dataclass(frozen=True)
class Model:
    layers: Tuple[Layer, ...]
    d_model: int
    vocab: int
    linear_bits: Optional[int]      # 8: W8A8 linears; None: bf16

    def groups(self) -> List[Tuple[Layer, int]]:
        """Each distinct layer with its number, in order of appearance:
        a call is counted once per group and multiplied, as the layer scan
        repeats it."""
        return list(collections.Counter(self.layers).items())


@dataclasses.dataclass
class Calls:
    """Kernel calls of a window by kernel class, each as (operations,
    bytes), with the precision each class's roofline is taken at; model
    operations by precision ({"bf16": ops, "int8": ops}); tokens."""
    calls: Dict[str, List[Tuple[float, float]]] = dataclasses.field(
        default_factory=dict)
    precision: Dict[str, str] = dataclasses.field(default_factory=dict)
    model_ops: Dict[str, float] = dataclasses.field(default_factory=dict)
    tokens: int = 0

    def of(self, *classes: str) -> List[Tuple[float, float]]:
        """The calls of these classes, one list after the other."""
        return [c for k in classes for c in self.calls.get(k, [])]

    def add(self, cls: str, precision: str,
            calls: List[Tuple[float, float]]) -> None:
        self.calls.setdefault(cls, []).extend(calls)
        self.precision[cls] = precision

    def add_ops(self, precision: str, ops: float) -> None:
        self.model_ops[precision] = self.model_ops.get(precision, 0.0) + ops


def _attn_call(a: Attention, rows: List[Tuple[int, int]]
               ) -> Tuple[float, float]:
    """One layer's attention over rows of (first query position, number
    of queries): each query at position p reads p + 1 keys; K and V of the
    longest context of each row are read once."""
    ops = by = 0.0
    for lo, n in rows:
        hi = lo + n
        keys = n * lo + n * (n + 1) / 2          # sum over p of (p + 1)
        ops += 4 * a.heads * a.head_dim * keys
        by += (2 * hi * a.kv_heads * (a.head_dim * a.kv_bytes
                                      + a.kv_scale_bytes)
               + 2 * n * a.heads * a.head_dim * 2)     # q in, out (bf16)
    return ops, by


def _step(m: Model, groups: List[Tuple[Layer, int]],
          rows: List[Tuple[int, int]], c: Calls) -> None:
    """Every layer on rows of (first query position, number of queries):
    one attention call and one call of each extra class over the layers
    that have them, and the linears of every layer on the live tokens.
    ``groups``: ``m.groups()``."""
    tokens = sum(n for _, n in rows)
    prec = "int8" if m.linear_bits else "bf16"
    attn = [(_attn_call(layer.attention, rows), count)
            for layer, count in groups if layer.attention is not None]
    if attn:
        ops = sum(o * count for (o, _), count in attn)
        c.add(ATTENTION, "bf16",
              [(ops, sum(b * count for (_, b), count in attn))])
        c.add_ops("bf16", ops)
    extra: Dict[str, list] = {}
    for layer, count in groups:
        for lin in layer.linears:
            ops = 2.0 * tokens * lin.k * lin.n
            c.add_ops(prec, ops * count)
            if m.linear_bits:
                by = (lin.k * lin.n + 4 * lin.n + tokens * lin.k + 4 * tokens
                      + 2 * tokens * lin.n)
                c.add(W8A8_MATMUL, prec, [(ops, float(by))] * count)
                c.add(W8A8_QUANTIZE, prec,
                      [(0.0, float(3 * tokens * lin.k + 4 * tokens))] * count)
        for e in layer.extra:
            acc = extra.setdefault(e.kernel, [e.precision, 0.0, 0.0])
            acc[1] += e.ops_per_token * tokens * count
            acc[2] += (e.bytes_per_token * tokens
                       + e.bytes_per_row * len(rows)) * count
            c.add_ops(e.precision, e.ops_per_token * tokens * count)
    for cls, (p, ops, by) in extra.items():
        c.add(cls, p, [(ops, by)])
    c.tokens += tokens


def _logits(m: Model, rows: int, c: Calls) -> None:
    c.add_ops("bf16", 2.0 * rows * m.d_model * m.vocab)


def count(m: Model, spans: List[dict], prompt_len: Dict[int, int],
          start: float, end: float) -> Calls:
    """The calls whose dispatch ended inside [start, end). ``spans`` are the
    engine's span records, ``prompt_len`` maps uid -> prompt length."""
    c, groups = Calls(), m.groups()
    emitted: Dict[int, int] = {}          # uid -> tokens emitted so far
    dispatches: Dict[Tuple[float, float], list] = {}
    for s in sorted((r for r in spans if r["type"] == "span"),
                    key=lambda r: r["t1"]):
        uid, args = s["uid"], s["args"]
        inside = start <= s["t1"] < end
        if s["name"] == "prefill":
            lo, hi = args["lo"], args["hi"]
            if inside:
                _step(m, groups, [(lo, hi - lo)], c)
                if args.get("tokens"):
                    _logits(m, 1, c)
            if args.get("tokens"):
                emitted[uid] = emitted.get(uid, 0) + args["tokens"]
        elif s["name"] == "decode":
            pos = prompt_len.get(uid, 0) + emitted.get(uid, 0) - 1
            n = args["tokens"]
            emitted[uid] = emitted.get(uid, 0) + n
            if inside and uid in prompt_len:
                dispatches.setdefault((s["t0"], s["t1"]), []).append(
                    (pos, n))
    for rows in dispatches.values():
        for j in range(max(n for _, n in rows)):
            live = [(p + j, 1) for p, n in rows if n > j]
            _step(m, groups, live, c)
            _logits(m, len(live), c)
    return c


def least_time(calls: List[Tuple[float, float]], peak_ops: float,
               peak_bw: float) -> Tuple[float, str]:
    """The least time the chip could take for the calls, each bounded by
    the larger of ops / peak and bytes / bandwidth; and which bound holds
    for most of that time."""
    t_ops = t_mem = 0.0
    for ops, by in calls:
        a, b = ops / peak_ops, by / peak_bw
        if a >= b:
            t_ops += a
        else:
            t_mem += b
    return t_ops + t_mem, "compute" if t_ops >= t_mem else "memory"


def roofline_share(rec: dict, kernels: List[str]) -> Optional[float]:
    """A kernel's share of its roofline, in %: the least time the chip
    could take for its calls in the traced stretch, at the peak of the
    precision the work model gives the classes, over the device time of
    its operations there (``kernels``: ``devtrace.kernel_of`` classes).
    None where the stretch holds no such call or operation."""
    from bench import devtrace
    if rec["trace"] is None:
        return None
    wt = rec["work_trace"]
    calls = wt.of(*kernels)
    dev = sum(devtrace.kernel_seconds(rec["trace"], k) for k in kernels)
    if not dev or not calls:
        return None
    precision, = {wt.precision[k] for k in kernels if k in wt.precision}
    peaks = rec["peaks"]
    least, _ = least_time(calls, peaks["flops"][precision],
                          peaks["hbm_bytes_per_s"])
    return 100.0 * least / dev
