from multidisttorch_tpu.utils.imaging import save_image_grid
from multidisttorch_tpu.utils.logging import log0
from multidisttorch_tpu.utils.profiling import profile_trace, trial_timer
