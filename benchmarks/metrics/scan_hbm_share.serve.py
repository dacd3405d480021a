"""The served twin of ``scan_hbm_share.store``: how near the device time of
a served store's deps flushes comes to the bandwidth bound of an
index-driven scan.  Bytes: lib/scan_bytes.py's for each store-level flush a
device route answered inside the traced slice (solo device parts and fused
members), at the stores' mean live slots, with the slice's queries and
downloaded bytes spread over those flushes.  Time: the slice's device busy
seconds, ALL device operations in it (the flushes' table syncs and the
stores' audit ticks among them), so this is a lower bound of the flushes'
own share.  None where no flush went to the device in the slice."""

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "commit_rate"


def read(record):
    trace = record.get("trace")
    dev = record.get("slice_device")
    if record.get("driver") != "served" or not trace or not dev \
            or not dev["flushes"] or not trace["busy_s"]:
        return None
    from benchmarks.lib.peaks import peak
    from benchmarks.lib.scan_bytes import flush_bytes
    n = dev["flushes"]
    need = n * flush_bytes(dev["live_slots"], dev["intervals_per_slot"],
                           dev["queries"] / n, dev["query_intervals"],
                           dev["download_bytes"] / n)
    return 100.0 * need / (peak(record["device_kind"], "hbm_bytes_per_s")
                           * trace["busy_s"])
