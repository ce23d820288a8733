from repro.serve.ann import BallTree, brute_force_nearest  # noqa: F401
from repro.obs import StageTimers  # noqa: F401
from repro.serve.engine import ServeEngine  # noqa: F401
from repro.serve.proxy_service import (  # noqa: F401
    ProxyService, QueryResult, StaleServiceError,
)
