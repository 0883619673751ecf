"""Making every trial's weights and optimizer state on its chip from
the seed (``create_lm_state`` traced into one program per trial)."""

LAYER = "entry points"
UNIT = "s"
MOVES = "setup_s"


def read(record: dict):
    return record["spans"].get("state_init_s")
