"""Host time per flush of pricing the flush's routes
(``DeviceState._choose_route``: the live-tail estimate against the
calibration's costs): ``kernel_times`` ``choose_route`` over the window /
flushes.  HOST clock; needs no trace.  None from a program without the
kind."""

LAYER = "device dispatch"
UNIT = "ms/flush"
SOURCE = "program_span"
MOVES = "preaccept_rate"


def read(record):
    if record.get("driver") != "store" or not record["flushes"]:
        return None
    cell = record["counters"]["kernel_times"].get("choose_route")
    if cell is None:
        return None
    return cell[1] * 1e3 / record["flushes"]
