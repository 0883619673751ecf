"""Seconds in backend compile or in retrieval from the persistent cache
during set-up (``compile_book.py``)."""

LAYER = "compile"
UNIT = "s"
MOVES = "setup_s"


def read(record: dict):
    return record["compile_setup"]["compile_s"]
