"""Share of the traced part in which no operation ran on the device,
on the idlest chip (``trace_reduce.py``)."""

LAYER = "device"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"


def read(record: dict):
    if record["trace"] is None:
        return None
    return 100.0 * record["trace"]["idle_share_worst"]
