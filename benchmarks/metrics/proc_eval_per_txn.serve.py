"""Host time of the stored procedures per txn answered: SELF milliseconds
of the program's ``srv.proc`` spans (a procedure txn's one evaluation over
its reads, for its writes and its result: ``coordinate/execute.py``, where
recovery re-executes too) on the loop's thread in the traced slice /
``srv.client_reply`` spans in the slice (lib/program_spans.py).  None from
a program without the span, or where no procedure ran in the slice."""

LAYER = "server loop + protocol"
UNIT = "ms/txn"
SOURCE = "program_span"
MOVES = "commit_rate"

NAME = "srv.proc"


def read(record):
    from benchmarks.lib import program_spans
    if record.get("driver") != "served":
        return None
    red = program_spans.spans()
    if red is None or not any(cell["count"].get(NAME)
                              for cell in red["threads"].values()):
        return None
    return program_spans.ms_per_reply(record, lambda name: name == NAME)
