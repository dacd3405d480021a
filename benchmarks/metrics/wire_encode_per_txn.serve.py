"""Host time the serving loop spends sending, per txn answered: self
milliseconds of the program's ``srv.flush_tick`` (the end-of-tick flush:
envelopes built, each peer frame's ``encode_packet``, client writes joined)
and ``srv.client_reply`` (a reply frame encoded and queued) spans on the loop's thread in the traced slice / ``srv.client_reply`` spans
in the slice (lib/program_spans.py).  None from a program that exports no
spans."""

LAYER = "client / wire"
UNIT = "ms/txn"
SOURCE = "program_span"
MOVES = "commit_rate"

NAMES = ("srv.flush_tick", "srv.client_reply")


def read(record):
    from benchmarks.lib import program_spans
    return program_spans.ms_per_reply(record, lambda name: name in NAMES)
