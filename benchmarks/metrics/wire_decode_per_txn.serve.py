"""Host time the serving loop spends decoding what arrives, per txn answered:
self milliseconds of the program's ``srv.decode`` spans (a frame's
``peek_header`` + ``decode_payload`` in ``NodeServer._on_payload``, a
protocol payload's ``wire.decode`` in ``maelstrom/node.py``) on the loop's
thread in the traced slice / ``srv.client_reply`` spans in the slice
(lib/program_spans.py).  None from a program that exports no spans."""

LAYER = "client / wire"
UNIT = "ms/txn"
SOURCE = "program_span"
MOVES = "commit_rate"


def read(record):
    from benchmarks.lib import program_spans
    return program_spans.ms_per_reply(
        record, lambda name: name == "srv.decode")
