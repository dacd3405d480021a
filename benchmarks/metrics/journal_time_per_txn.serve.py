"""Host time the serving loop spends inside the journal per txn answered:
self milliseconds of the program's ``srv.journal.sync`` spans
(``journal/commit.py`` flush cycle: drain of the parked rows, an inline
fsync, account) on the LOOP's thread in the traced slice /
``srv.client_reply`` spans in the slice (lib/program_spans.py).  The
offloaded fsync, which the workers wait out beside the loop, is
``journal_fsync_wall_per_txn.serve``; the appends are no span
(``stats()["loop"]["srv.journal.append"]`` counts them), so their time is
their caller's here.  None from a program that exports no spans."""

LAYER = "journal"
UNIT = "ms/txn"
SOURCE = "program_span"
MOVES = "commit_p95"


def read(record):
    from benchmarks.lib import program_spans
    return program_spans.ms_per_reply(
        record, lambda name: name.startswith("srv.journal."))
