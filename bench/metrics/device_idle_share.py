"""Device: share of the traced stretch with no operation on the chip,
1 - (union of device operation intervals / stretch)."""
from bench import devtrace

UNIT = "%"


def read(rec):
    tr = rec["trace"]
    if tr is None or not tr["ops"]:
        return None
    return 100.0 * (1.0 - devtrace.busy_s(tr) / tr["window_s"])
