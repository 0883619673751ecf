"""Host time to draw one batch from the corpus and hand it to the
device, median over the window. Hidden behind the step in flight until
the inputs of one round take longer than the round."""

import statistics

LAYER = "input"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"


def read(record: dict):
    if not record["input_s"]:
        return None
    return 1e3 * statistics.median(record["input_s"])
