"""Programs compiled and written to the persistent cache during set-up:
0 in every run of a checkout after the first."""

LAYER = "compile"
UNIT = "count"
MOVES = "setup_s"


def read(record: dict):
    return record["compile_setup"]["misses"]
