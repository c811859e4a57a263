"""Model step: the model operations the measured window needed, each over
the chip's peak for its precision, over the window's length."""
UNIT = "%"


def read(rec):
    t0, t1 = rec["window"]
    work = rec["work_window"]
    if not work.tokens:
        return None
    peaks = rec["peaks"]
    least = sum(ops / peaks["flops"][prec]
                for prec, ops in work.model_ops.items())
    return 100.0 * least / (t1 - t0)
