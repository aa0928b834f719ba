"""Model serving: production-hardened JSON HTTP inference.

Reference: ``deeplearning4j-remote`` / ``nd4j-remote`` ``JsonModelServer``
(SURVEY §2.6 S7): HTTP endpoint wrapping MLN/CG/SameDiff (and
ParallelInference for batching) with typed (de)serializers.

Layered as: ``JsonModelServer`` (HTTP, admission control, deadlines,
liveness/readiness, graceful drain) over ``BatchingInferenceExecutor``
(bounded queue, micro-batching, warmup, chaos hooks) over
``parallel.ParallelInference`` (bucketed padded batches on one sharded
executable). See docs/PARITY.md "Serving" for the DL4J mapping.
"""

from .executor import (BatchingInferenceExecutor, DeadlineExceededError,
                       ExecutorClosedError, GenerationFuture,
                       GenerativeInferenceExecutor, InferenceFuture,
                       QueueFullError, StepAtDispatch)
from .json_server import JsonModelServer, JsonModelClient
from .loadgen import Burst, LoadGenerator, TraceSpec, replay
from .pool import PoolAutoscaler, ServingPool

__all__ = [
    "JsonModelServer",
    "JsonModelClient",
    "BatchingInferenceExecutor",
    "GenerativeInferenceExecutor",
    "GenerationFuture",
    "StepAtDispatch",
    "InferenceFuture",
    "QueueFullError",
    "DeadlineExceededError",
    "ExecutorClosedError",
    "Burst",
    "LoadGenerator",
    "TraceSpec",
    "replay",
    "ServingPool",
    "PoolAutoscaler",
]
