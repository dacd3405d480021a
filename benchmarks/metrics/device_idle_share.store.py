"""Share of the traced slice in which no operation ran on the device:
1 - busy / slice, from the jax.profiler trace (lib/trace_reduce.py).  A trace
with no device event at all is idle 100 %, not an error."""

LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "preaccept_rate"


def read(record):
    trace = record.get("trace")
    if record.get("driver") != "store" or not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
