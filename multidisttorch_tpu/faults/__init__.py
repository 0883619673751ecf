"""Deterministic fault injection for chaos-testing trial supervision.

``plan`` defines the serializable schedule (:class:`FaultPlan` /
:class:`FaultSpec`); ``inject`` interprets it at run time
(:class:`FaultInjector`) through hooks the HPO driver threads through
itself, the step dispatch, and the data iterators; ``harness`` runs the
standard chaos protocol behind ``tools/chaos_run.py`` (asserted in
``tests/test_telemetry.py`` and ``tests/test_elastic.py``). See docs/RESILIENCE.md for the failure taxonomy
and how to write a plan.
"""

from multidisttorch_tpu.faults.plan import (  # noqa: F401
    ALL_KINDS,
    CKPT_CORRUPT,
    CRASH,
    DATA_ERROR,
    DIVERGE,
    HOST_KINDS,
    HOST_LOST,
    INFRA_KINDS,
    PREEMPT,
    SLOW,
    WEDGE,
    FaultPlan,
    FaultSpec,
)
from multidisttorch_tpu.faults.inject import (  # noqa: F401
    HOST_LOST_EXIT_CODE,
    DataFault,
    FaultInjector,
    HostPreemption,
    InfraFault,
    InjectedCrash,
    corrupt_file,
)
