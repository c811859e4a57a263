"""Kernel: cache attention (prefill and decode share one Pallas kernel),
needed work over its device time, as a share of the bf16 roofline."""
from bench.work import roofline_share

UNIT = "%"


def read(rec):
    return roofline_share(rec, ["attention"])
