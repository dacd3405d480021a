"""Seconds jax spent tracing, lowering and compiling INSIDE the measured
window (jax.monitoring /jax/core/compile/* durations).  Should be 0: every
shape is warmed in set-up."""

LAYER = "start-up"
UNIT = "s"
SOURCE = "program_span"
MOVES = "preaccept_rate"


def read(record):
    if record.get("driver") != "store":
        return None
    return record["compile"]["seconds"]
