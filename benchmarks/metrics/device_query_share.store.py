"""Share of the window's deps queries that a device route answered:
(bucketed + dense + fused + mesh) / (those + host), from the DeviceStates'
route counters.  0 % means the router priced every scan to the host."""

LAYER = "device dispatch"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "preaccept_rate"

def read(record):
    if record.get("driver") != "store":
        return None
    from benchmarks.lib.checks import DEVICE_ROUTES
    c = record["counters"]
    on_device = sum(c[k] for k in DEVICE_ROUTES)
    total = on_device + c["n_host_queries"]
    return 100.0 * on_device / total if total else None
