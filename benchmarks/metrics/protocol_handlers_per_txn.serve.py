"""Host time of the protocol itself per txn answered: SELF milliseconds of
the program's ``srv.txn`` (a client txn, admission to hand-off),
``srv.req.*`` / ``srv.rsp.*`` (protocol requests and replies delivered to the
node), ``srv.store_setup`` / ``srv.handler`` (a command store's drain and the
handler bodies in it) and ``srv.timer`` (scheduler callbacks) spans on the
loop's thread in the traced slice / ``srv.client_reply`` spans in the slice
(lib/program_spans.py).  Self time: the deps flush, the journal, decode and
encode nested under a handler are their own spans and are not counted here.
None from a program that exports no spans."""

LAYER = "server loop + protocol"
UNIT = "ms/txn"
SOURCE = "program_span"
MOVES = "commit_rate"

NAMES = ("srv.txn", "srv.store_setup", "srv.handler", "srv.timer")
FAMILIES = ("srv.req.", "srv.rsp.")


def read(record):
    from benchmarks.lib import program_spans
    return program_spans.ms_per_reply(
        record, lambda name: name in NAMES or name.startswith(FAMILIES))
