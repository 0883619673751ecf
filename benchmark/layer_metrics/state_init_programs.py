"""Programs that making a trial's state sent to the backend, each
traced, lowered and loaded or compiled by itself (``model.init`` and
``tx.init`` run eagerly): the ``backend`` entries of the program's
compile log inside the spans ``admit:init_state`` that ended between the
entry's call and the stamp that opens the window."""

LAYER = "entry points"
UNIT = "count"
MOVES = "setup_s"


def read(record: dict):
    try:
        from multidisttorch_tpu.train.lm import STEP_PROGRAM
        from multidisttorch_tpu.utils.profiling import admission_split
    except ImportError:  # a program from before the compile log
        return None
    split = admission_split(STEP_PROGRAM, record["t_entry"], record["stamps"][0])
    return None if split is None else split["init_programs"]
