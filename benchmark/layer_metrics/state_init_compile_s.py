"""What of ``state_init_s`` went into tracing, lowering and loading or
compiling the state's programs: the outermost ``trace``, ``lower`` and
``backend`` seconds of the program's compile log inside the spans
``admit:init_state`` that ended between the entry's call and the stamp
that opens the window. The rest of ``state_init_s`` is those programs'
own run time and the host's."""

LAYER = "entry points"
UNIT = "s"
MOVES = "setup_s"


def read(record: dict):
    try:
        from multidisttorch_tpu.train.lm import STEP_PROGRAM
        from multidisttorch_tpu.utils.profiling import admission_split
    except ImportError:  # a program from before the compile log
        return None
    split = admission_split(STEP_PROGRAM, record["t_entry"], record["stamps"][0])
    if split is None:
        return None
    return split["init_trace_s"] + split["init_lower_s"] + split["init_load_s"]
