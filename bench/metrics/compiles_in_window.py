"""Device / compile: backend compilations (persistent-cache loads among
them) that JAX reported while the measured window was open. Set-up warms
every shape the traffic needs, so this should be 0."""
UNIT = "count"


def read(rec):
    t0, t1 = rec["window"]
    return float(sum(t0 <= t < t1 for t in rec["compile_times"]))
