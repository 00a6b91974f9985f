"""repro.serve: continuous-batching decode service under live routing
drift.

Public API:
    Request / RequestQueue   — length-bucketed admission (queue.py)
    ContinuousBatcher        — slot-based decode batch state (batcher.py)
    ServeEngine              — prefill/decode disaggregation, KV-aware
                               admission, device-controller loop with
                               schedule-regime warm-swap (engine.py)
    ServeMetrics             — serving telemetry (metrics.py)
    init_serve_params        — seeded weights in the serving dtype
"""

from repro.serve.batcher import ContinuousBatcher
from repro.serve.engine import ServeEngine, init_serve_params
from repro.serve.metrics import ServeMetrics, percentiles
from repro.serve.queue import Request, RequestQueue

__all__ = [
    "ContinuousBatcher",
    "Request",
    "RequestQueue",
    "ServeEngine",
    "ServeMetrics",
    "init_serve_params",
    "percentiles",
]
