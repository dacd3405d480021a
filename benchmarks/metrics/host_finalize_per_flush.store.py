"""Host time of the shared finalize per flush: ``kernel_times``
``host_attr_finalize`` (attributed entries -> builder CSRs, with the device
idle) over the window / flushes.  HOST clock; needs no trace."""

LAYER = "device dispatch"
UNIT = "ms/flush"
SOURCE = "program_span"
MOVES = "preaccept_rate"


def read(record):
    if record.get("driver") != "store" or not record["flushes"]:
        return None
    cell = record["counters"]["kernel_times"].get("host_attr_finalize")
    if cell is None:
        return None
    return cell[1] * 1e3 / record["flushes"]
