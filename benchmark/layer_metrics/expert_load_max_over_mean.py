"""How uneven the routing is over the experts held: the fullest
expert's assignments over the mean, from the step's own counter
(``metrics["expert_counts"]``), the worst expert layer of each step,
the median over the window's steps. 1 is an even load; the grouped
matrix products take as long as their tiles, so a skew costs
``experts_ms``."""

from benchmark import moe_scopes

LAYER = "step programs"
UNIT = "ratio"
MOVES = "tokens_per_s_per_chip"


def read(record: dict):
    return moe_scopes.load_max_over_mean(record)
