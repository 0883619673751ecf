"""Process start to the stamp that opens the first measured reading:
imports, backend start, data, weights, loading or compiling the
programs, the warm rounds. The comparison with the reference runs after
the window and is not part of it."""

UNIT = "s"


def read(record: dict):
    return record["stamps"][0] - record["t_process_start"]
