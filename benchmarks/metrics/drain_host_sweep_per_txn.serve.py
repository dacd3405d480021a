"""Host time the PRICED host sweeps of the stores' drain ticks cost the
serving loop per acknowledged txn: ``kernel_times`` ``drain_tick_host`` (a
tick whose live set the router priced cheaper to sweep in Python than to send
round the device: DeviceState._host_tick_pays) over the window / txn_ok in
it.  HOST clock.  What the priced route costs, beside
``drain_tick_per_txn.serve``, which is what the device ticks it replaces
cost; calls of ``drain_tick_host`` against calls of ``drain_tick_wait`` is
the share of ticks it took.  0.0 on a program that has no such kind."""

LAYER = "device dispatch"
UNIT = "ms/txn"
SOURCE = "program_span"
MOVES = "commit_rate"


def read(record):
    if record.get("driver") != "served" or not record["acked"]:
        return None
    _calls, secs = record["counters"]["kernel_times"].get(
        "drain_tick_host", (0, 0.0))
    return secs * 1e3 / record["acked"]
