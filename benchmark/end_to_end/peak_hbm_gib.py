"""Largest ``memory_stats()["peak_bytes_in_use"]`` over the cell's
chips, read after the window."""

UNIT = "GiB"


def read(record: dict):
    return record["peak_bytes"] / 2**30
