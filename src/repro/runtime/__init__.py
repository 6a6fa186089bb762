from repro.runtime.agreement import (  # noqa: F401
    AgreementChecker,
    DivergenceError,
    FileTransport,
    Transport,
    exchange,
    fingerprint,
    step_fingerprint,
)
from repro.runtime.chaos import (  # noqa: F401
    ChaosMonkey,
    Preemption,
    StepGuard,
    TransientFault,
)
from repro.runtime.fault_tolerance import (  # noqa: F401
    ElasticPlan,
    HeartbeatTracker,
    PreemptionGuard,
    TrainSupervisor,
)
from repro.runtime.metrics import GuardMetrics, ServeMetrics  # noqa: F401
from repro.runtime.serving import (  # noqa: F401
    AdmissionQueue,
    CircuitBreaker,
    Completion,
    DeadlineExceeded,
    Request,
    RequestRecord,
    RequestRejected,
    ServingRuntime,
    guarded_logit_stat,
)
