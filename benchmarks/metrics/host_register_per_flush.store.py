"""Host time of registering a flush's txns in the device mirror per flush:
``kernel_times`` ``register`` (``DeviceState.register``, one span a call:
slot, intervals, status) over the window / flushes.  HOST clock; needs no
trace.  None from a program without the kind."""

LAYER = "device dispatch"
UNIT = "ms/flush"
SOURCE = "program_span"
MOVES = "preaccept_rate"


def read(record):
    if record.get("driver") != "store" or not record["flushes"]:
        return None
    cell = record["counters"]["kernel_times"].get("register")
    if cell is None:
        return None
    return cell[1] * 1e3 / record["flushes"]
