"""Engine and front door (host): share of the traced stretch in which the
device idles while the front door's pump is not parked for want of work
(outside every ``repro.door.idle`` annotation), so idle time the host
causes and idle time no request causes are told apart
(``bench/progtrace.py``)."""
from bench import progtrace

UNIT = "%"


def read(rec):
    pt = progtrace.from_run(rec)
    return None if pt is None else progtrace.host_bound_idle_share(pt)
