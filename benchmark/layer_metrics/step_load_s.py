"""The backend's part of the step program's first call: the cache key,
the read and the deserialisation on a warm run, the compile on a cold
one. The ``backend`` seconds the program's compile log holds for
``train.lm.STEP_PROGRAM`` between the entry's call and the stamp that
opens the window, summed over the cell's trials. A progress line says
how much of it was the cache's read (``retrieval``; 0 on a miss) and
what the log holds for every program of set-up, beside ``compile_s``."""

LAYER = "compile"
UNIT = "s"
MOVES = "setup_s"


def read(record: dict):
    try:
        from multidisttorch_tpu.train.lm import STEP_PROGRAM
        from multidisttorch_tpu.utils.compile_cache import compile_log
        from multidisttorch_tpu.utils.profiling import admission_split
    except ImportError:  # a program from before the compile log
        return None
    opened = record["stamps"][0]
    split = admission_split(STEP_PROGRAM, record["t_entry"], opened)
    if split is None:
        return None
    backend = sum(
        stages["backend"].secs
        for stages in compile_log().by_program(None, opened).values()
        if "backend" in stages
    )
    print(
        "[benchmark] admission " + " ".join(f"{k}={v:.3f}" for k, v in split.items())
        + f"; the log's backend seconds to the window's opening {backend:.3f}"
        + f" (compile_s {record['compile_setup']['compile_s']:.3f})",
        flush=True,
    )
    return split["step_load_s"]
