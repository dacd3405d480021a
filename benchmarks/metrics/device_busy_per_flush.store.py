"""Device time per flush: the union of the device operations' intervals in
the traced slice / the flushes in it (jax.profiler trace,
lib/trace_reduce.py)."""

LAYER = "kernels"
UNIT = "ms/flush"
SOURCE = "device_trace"
MOVES = "preaccept_rate"


def read(record):
    trace = record.get("trace")
    if record.get("driver") != "store" or not trace \
            or not record["slice_flushes"]:
        return None
    return trace["busy_s"] * 1e3 / record["slice_flushes"]
