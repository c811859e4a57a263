"""KV state pool: share of device busy time in the traced stretch spent in
the pool's operations: those under the program's ``kv_*`` scopes (the slot
pool's gather, scatter, select and reset, the KV cache write) and every
copy, slice or write-back of the arena the engine's executables update in
place, scoped or not (the layer scan carrying it, XLA's copies), from the
HLO the profile holds (``bench/progtrace.py``, ``in_pool``)."""
from bench import progtrace

UNIT = "%"


def read(rec):
    pt = progtrace.from_run(rec)
    return None if pt is None else progtrace.kv_pool_share(pt)
