"""Engine / scheduler: share of device busy time in the traced stretch
spent in the prefill executables (``jit_engine_prefill``,
``jit_engine_spec_prefill``), by the names the program gives them in its
own profile (``bench/progtrace.py``)."""
from bench import progtrace

UNIT = "%"


def read(rec):
    pt = progtrace.from_run(rec)
    return None if pt is None else progtrace.prefill_device_share(pt)
