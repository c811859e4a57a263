"""Find a cell's knee: the highest arrival rate it sustains.

    python bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 3,4,5,6

One process builds the cell's system once, then serves one window at each
rate in turn (the cell's mix with ``rate_per_s`` replaced) and prints one
line per rate: the end-to-end tails, the median TTFT of the window's first
and second half, and how long the server needed after the window to finish
what was due in it. A rate the server sustains keeps both halves alike and
finishes within about one request's service time; above the knee the
backlog grows all through the window. The cell's rate is then set, once,
to 0.8 x the knee in its mix file. Not run by the driver.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from run import (BENCH, end_to_end, load_cell, load_module, log,  # noqa
                 open_system, serve_window)
from bench import stats


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    rates = [float(r) for r in args.rates.split(",")]
    gen = load_module(BENCH / "traffic" / f"{cell['mix']['kind']}.py")
    vocab = cell["config"]["model"]["vocab_size"]
    plans = [gen.generate(dict(cell["mix"], rate_per_s=r), args.seed + i,
                          args.seconds, vocab) for i, r in enumerate(rates)]
    S = open_system(cell, args.seed, 0, warm_plan=plans[-1])
    log(f"[sweep] set-up {time.monotonic() - S.t_start:.1f}s")
    for rate, plan in zip(rates, plans):
        window, drain_end, recs = serve_window(S, plan, args.seconds)
        e2e, attempted, failed = end_to_end(recs, window, drain_end)
        mid = (window[0] + window[1]) / 2
        half = lambda a, b: stats.nearest_rank(
            [r["t_first"] - r["due"] for r in recs
             if a <= r["due"] < b and r["t_first"] is not None], 50)
        due = [r for r in recs if window[0] <= r["due"] < window[1]]
        last = max((r["t_done"] or drain_end) for r in due)
        print(json.dumps({
            "rate": rate, "attempted": attempted, "failed": failed,
            **{k: v for k, v in e2e.items()},
            "ttft_p50_first_half_ms": 1e3 * (half(window[0], mid) or 0),
            "ttft_p50_second_half_ms": 1e3 * (half(mid, window[1]) or 0),
            "drain_s": last - window[1]}), flush=True)
        time.sleep(1.0)
    S.dt.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
