"""WALL time of the journal's offloaded fsyncs per txn answered: self
milliseconds of the program's ``srv.journal.sync`` spans on every thread
but the loop's (``GroupCommit._flush_async``'s worker) in the traced slice /
``srv.client_reply`` spans in the slice (lib/program_spans.py).  No CPU and
not the loop's time: the workers block in ``fsync`` while the loop goes on,
so this overlaps the other ``.serve`` span metrics and is never summed with
them; it is what a reply gated on durability waits for.  None from a program
that exports no spans."""

LAYER = "journal"
UNIT = "ms/txn"
SOURCE = "program_span"
MOVES = "commit_p95"


def read(record):
    from benchmarks.lib import program_spans
    return program_spans.ms_per_reply(
        record, lambda name: name.startswith("srv.journal."), off_loop=True)
