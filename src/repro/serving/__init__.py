from repro.serving.forest import (  # noqa: F401
    ForestServeBundle,
    MicroBatcher,
    make_forest_server,
)
from repro.serving.faults import (  # noqa: F401
    FakeClock,
    FaultPlan,
    FaultyPredictor,
)
from repro.serving.server import (  # noqa: F401
    AsyncForestServer,
    CircuitBreaker,
    ForestServer,
    RequestFailed,
    RequestShed,
    RequestTimedOut,
    RetryPolicy,
    ServerMetrics,
)
