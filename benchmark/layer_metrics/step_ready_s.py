"""First dispatch of the step program to the end of the warm rounds:
tracing and lowering it, compiling it or loading it from the persistent
cache, and the warm steps themselves."""

LAYER = "entry points"
UNIT = "s"
MOVES = "setup_s"


def read(record: dict):
    return record["spans"].get("step_ready_s")
