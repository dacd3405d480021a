"""Seconds the host spent waiting on the device per flush: the sum of
DeviceState.kernel_times' ``wait_*`` entries over the window / flushes.
HOST clock around the download joins — the time the host waited, NOT kernel
time (that is device_busy_per_flush.store, from the trace)."""

LAYER = "device dispatch"
UNIT = "ms/flush"
SOURCE = "program_span"
MOVES = "preaccept_rate"


def read(record):
    if record.get("driver") != "store" or not record["flushes"]:
        return None
    waited = sum(secs for kind, (_calls, secs)
                 in record["counters"]["kernel_times"].items()
                 if kind.startswith("wait_"))
    return waited * 1e3 / record["flushes"]
