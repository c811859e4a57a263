"""The work a served window needs, counted from the engine's spans.

A roofline share or a utilisation counts the operations and bytes the
algorithm needs for the tokens actually processed, never what an
implementation happens to do: attention over each request's real context
length (not the bucketed window, padded query rows or block sizes), matrix
products over the live rows of a step (not the idle slots of the batch),
logits only where a token is produced. So the count stays the same
whatever implements a kernel.

The engine's spans (``telemetry.SpanRecorder``) say what each dispatch
processed: a ``prefill`` span covers prompt positions [lo, hi) of one
request; the ``decode`` spans of one dispatch share its start and end, and
each says how many tokens its request emitted in the dispatch's scan.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class Model:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    linear_bits: Optional[int]      # 8: W8A8 linears; None: bf16
    kv_bytes: int                   # bytes per cached K or V element
    kv_scale_bytes: int             # bytes of scales per (position, head)

    def linears(self) -> List[Tuple[int, int]]:
        """(K, N) of each linear of one layer."""
        d, q, kv, f = (self.d_model, self.heads * self.head_dim,
                       self.kv_heads * self.head_dim, self.d_ff)
        return [(d, q), (d, kv), (d, kv), (q, d), (d, f), (d, f), (f, d)]


@dataclasses.dataclass
class Calls:
    """Kernel calls of a window, each as (operations, bytes)."""
    attention: List[Tuple[float, float]] = dataclasses.field(
        default_factory=list)
    matmul: List[Tuple[float, float]] = dataclasses.field(
        default_factory=list)
    quantize: List[Tuple[float, float]] = dataclasses.field(
        default_factory=list)
    # model operations by precision: {"bf16": ops, "int8": ops}
    model_ops: Dict[str, float] = dataclasses.field(default_factory=dict)
    tokens: int = 0


def _attn_call(m: Model, rows: List[Tuple[int, int]]) -> Tuple[float, float]:
    """One attention call (all layers) over rows of (first query position,
    number of queries): each query at position p reads p + 1 keys; K and V
    of the longest context of each row are read once."""
    ops = by = 0.0
    for lo, n in rows:
        hi = lo + n
        keys = n * lo + n * (n + 1) / 2          # sum over p of (p + 1)
        ops += 4 * m.heads * m.head_dim * keys
        by += (2 * hi * m.kv_heads * (m.head_dim * m.kv_bytes
                                      + m.kv_scale_bytes)
               + 2 * n * m.heads * m.head_dim * 2)     # q in, out (bf16)
    return ops * m.layers, by * m.layers


def _linear_calls(m: Model, rows: int, c: Calls) -> None:
    """The linears of every layer on ``rows`` live tokens."""
    prec = "int8" if m.linear_bits else "bf16"
    for k, n in m.linears():
        ops = 2.0 * rows * k * n
        c.model_ops[prec] = c.model_ops.get(prec, 0.0) + ops * m.layers
        if m.linear_bits:
            by = k * n + 4 * n + rows * k + 4 * rows + 2 * rows * n
            c.matmul += [(ops, float(by))] * m.layers
            c.quantize += [(0.0, float(3 * rows * k + 4 * rows))] * m.layers


def _logits(m: Model, rows: int, c: Calls) -> None:
    c.model_ops["bf16"] = (c.model_ops.get("bf16", 0.0)
                           + 2.0 * rows * m.d_model * m.vocab)


def count(m: Model, spans: List[dict], prompt_len: Dict[int, int],
          start: float, end: float) -> Calls:
    """The calls whose dispatch ended inside [start, end). ``spans`` are the
    engine's span records, ``prompt_len`` maps uid -> prompt length."""
    c = Calls()
    emitted: Dict[int, int] = {}          # uid -> tokens emitted so far
    groups: Dict[Tuple[float, float], list] = {}
    for s in sorted((r for r in spans if r["type"] == "span"),
                    key=lambda r: r["t1"]):
        uid, args = s["uid"], s["args"]
        inside = start <= s["t1"] < end
        if s["name"] == "prefill":
            lo, hi = args["lo"], args["hi"]
            if inside:
                call = _attn_call(m, [(lo, hi - lo)])
                c.attention.append(call)
                c.model_ops["bf16"] = c.model_ops.get("bf16", 0.0) + call[0]
                _linear_calls(m, hi - lo, c)
                c.tokens += hi - lo
                if args.get("tokens"):
                    _logits(m, 1, c)
            if args.get("tokens"):
                emitted[uid] = emitted.get(uid, 0) + args["tokens"]
        elif s["name"] == "decode":
            pos = prompt_len.get(uid, 0) + emitted.get(uid, 0) - 1
            n = args["tokens"]
            emitted[uid] = emitted.get(uid, 0) + n
            if inside and uid in prompt_len:
                groups.setdefault((s["t0"], s["t1"]), []).append((pos, n))
    for rows in groups.values():
        for j in range(max(n for _, n in rows)):
            live = [(p + j, 1) for p, n in rows if n > j]
            ops, by = _attn_call(m, live)
            c.attention.append((ops, by))
            c.model_ops["bf16"] = c.model_ops.get("bf16", 0.0) + ops
            _linear_calls(m, len(live), c)
            _logits(m, len(live), c)
            c.tokens += len(live)
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


def roofline_share(rec: dict, kernels: List[str],
                   calls: List[Tuple[float, float]],
                   precision: str) -> Optional[float]:
    """A kernel's share of its roofline, in %: the least time the chip
    could take for its calls in the traced stretch over the device time of
    its operations there (``kernels``: ``devtrace.kernel_of`` classes).
    None where the stretch holds no such call or operation."""
    from bench import devtrace
    if rec["trace"] is None:
        return None
    dev = sum(devtrace.kernel_seconds(rec["trace"], k) for k in kernels)
    if not dev or not calls:
        return None
    peaks = rec["peaks"]
    least, _ = least_time(calls, peaks["flops"][precision],
                          peaks["hbm_bytes_per_s"])
    return 100.0 * least / dev
