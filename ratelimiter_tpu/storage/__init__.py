from ratelimiter_tpu.storage.base import RateLimitStorage
from ratelimiter_tpu.storage.breaker import CircuitBreakerStorage
from ratelimiter_tpu.storage.chaos import FaultInjectingProxy, FaultInjectingStorage
from ratelimiter_tpu.storage.degraded import DegradedHostLimiter
from ratelimiter_tpu.storage.errors import (
    CircuitOpenError,
    RetryPolicy,
    StorageException,
)
from ratelimiter_tpu.storage.memory import InMemoryStorage
from ratelimiter_tpu.storage.retry import RetryingStorage
from ratelimiter_tpu.storage.tpu import TpuBatchedStorage, sharded_storage

__all__ = [
    "CircuitBreakerStorage",
    "CircuitOpenError",
    "DegradedHostLimiter",
    "FaultInjectingProxy",
    "FaultInjectingStorage",
    "RateLimitStorage",
    "InMemoryStorage",
    "RetryingStorage",
    "TpuBatchedStorage",
    "RetryPolicy",
    "StorageException",
    "sharded_storage",
]
