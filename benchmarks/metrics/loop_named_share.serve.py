"""How much of the serving loop's time the program names: the share of the
traced slice that the loop's thread spent under any ``srv.*`` span
(lib/program_spans.py: the union of those spans on the line that holds the
most of them).  The COVERAGE of the instrumentation, not a cost and no
mover: what is left is the event loop itself, socket reads and writes, and
the benchmark's clients, which share the thread.  A change that makes the
named work cheaper LOWERS it; read it beside the ms/txn span metrics to know
how much of the loop they account for, never as a gain or a loss
(BENCHMARK.json's ``better`` and ``moves`` are the form's, which has no
other way to say so).  None from a program that exports no
spans."""

LAYER = "server loop + protocol"
UNIT = "%"
SOURCE = "program_span"
MOVES = "commit_rate"


def read(record):
    from benchmarks.lib import program_spans
    if record.get("driver") != "served":
        return None
    red = program_spans.spans()
    if red is None or not red["window_s"]:
        return None
    return 100.0 * red["threads"][red["loop"]]["server_s"] / red["window_s"]
