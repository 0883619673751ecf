from multidisttorch_tpu.hpo.driver import (
    TrialConfig,
    TrialResult,
    all_completed,
    run_hpo,
)
from multidisttorch_tpu.hpo.ledger import SweepLedger, config_hash
from multidisttorch_tpu.hpo.pbt import PBTConfig, PBTResult, run_pbt
from multidisttorch_tpu.hpo.supervision import RetryPolicy, classify_failure
