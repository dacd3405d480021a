"""How near a flush's device time is to the bandwidth bound of an
index-driven scan: the bytes the flush must move (lib/scan_bytes.py, from
shapes and the downloaded result bytes) / (the chip's published HBM
bandwidth x device busy seconds per flush).  Not a kernel's roofline share:
the time is ALL device operations of the flush (no kernel is named in the
program yet), and the dense program is compare-bound on emulated int64,
for which no published peak exists."""

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "preaccept_rate"


def read(record):
    trace = record.get("trace")
    if record.get("driver") != "store" or not trace \
            or not record["slice_flushes"] or not trace["busy_s"]:
        return None
    from benchmarks.lib.peaks import peak
    from benchmarks.lib.scan_bytes import flush_bytes
    result = record["counters"]["download_bytes"] / record["flushes"]
    need = flush_bytes(record["live_slots"], record["intervals_per_slot"],
                       record["batch"], record["query_intervals"], result)
    busy = trace["busy_s"] / record["slice_flushes"]
    return 100.0 * need / (peak(record["device_kind"], "hbm_bytes_per_s")
                           * busy)
