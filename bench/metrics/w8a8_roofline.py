"""Kernel: the W8A8 matmuls and their activation quantize (HQP only),
needed work over their device time, as a share of the int8 roofline."""
from bench.work import roofline_share

UNIT = "%"


def read(rec):
    w = rec["work_trace"]
    return roofline_share(rec, ["w8a8_matmul", "w8a8_quantize"],
                          w.matmul + w.quantize, "int8")
