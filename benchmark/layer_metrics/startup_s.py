"""Process start to the entry being called: the interpreter, importing
jax and the program, the backend finding its chips, the cell's files."""

LAYER = "entry points"
UNIT = "s"
MOVES = "setup_s"


def read(record: dict):
    return record["t_entry"] - record["t_process_start"]
