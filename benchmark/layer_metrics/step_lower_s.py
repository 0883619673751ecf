"""Lowering the traced step to its MLIR module (Mosaic kernels
included): the ``lower`` seconds the program's compile log holds for
``train.lm.STEP_PROGRAM`` between the entry's call and the stamp that
opens the window, summed over the cell's trials."""

LAYER = "compile"
UNIT = "s"
MOVES = "setup_s"


def read(record: dict):
    try:
        from multidisttorch_tpu.train.lm import STEP_PROGRAM
        from multidisttorch_tpu.utils.profiling import admission_split
    except ImportError:  # a program from before the compile log
        return None
    split = admission_split(STEP_PROGRAM, record["t_entry"], record["stamps"][0])
    return None if split is None else split["step_lower_s"]
