"""Bytes one PreAccept flush has to move through HBM, from shapes alone.

This is the bandwidth bound of an INDEX-DRIVEN scan: every live slot's
interval bounds and ids are read once per flush, the query columns are read
once, and the result is written once.  The dense program the repo also has is
compare-bound on emulated int64, for which no published peak exists; nothing
here speaks for it."""

BOUND_BYTES = 16      # one interval: lo + hi, int64 each
ID_BYTES = 24         # one slot's id: msb + lsb int64, node + status int32


def flush_bytes(live_slots, intervals_per_slot, batch, query_intervals,
                result_bytes):
    """``live_slots`` x ``intervals_per_slot`` bounds + ``live_slots`` ids +
    the query matrix (``batch`` x ``query_intervals`` bounds + one bound id
    each) + the bytes the flush downloaded."""
    table = live_slots * (intervals_per_slot * BOUND_BYTES + ID_BYTES)
    queries = batch * (query_intervals * BOUND_BYTES + ID_BYTES)
    return table + queries + result_bytes
