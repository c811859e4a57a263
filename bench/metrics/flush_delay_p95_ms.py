"""Front door: 95th percentile (nearest rank) over the traced stretch's
``repro.door.flush`` annotations of ``t_engine - t_staged``, how long a
step's tokens waited between the pump staging them and the event loop
writing them toward the client (``bench/progtrace.py``)."""
from bench import progtrace

UNIT = "ms"


def read(rec):
    pt = progtrace.from_run(rec)
    return None if pt is None else progtrace.flush_delay_p95_ms(pt)
