"""Engine / scheduler: share of ``Engine.step`` wall time spent in ticks
that ran a prefill chunk, over the steps of the measured window (each
step's ``last_step`` record, taken by the benchmark's wrapper)."""
UNIT = "%"


def read(rec):
    t0, t1 = rec["window"]
    steps = [s for s in rec["steps"] if t0 <= s["t"] < t1]
    total = sum(s["wall_s"] for s in steps)
    if not total:
        return None
    pre = sum(s["wall_s"] for s in steps
              if "prefill_dispatch" in s["phases"])
    return 100.0 * pre / total
