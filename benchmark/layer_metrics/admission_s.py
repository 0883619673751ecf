"""Building the model to the end of the warm rounds, state
initialisation included: what a trial pays between being admitted and
training at pace."""

LAYER = "entry points"
UNIT = "s"
MOVES = "setup_s"


def read(record: dict):
    s = record["spans"]
    return s["model_build_s"] + s["state_init_s"] + s["step_ready_s"]
