"""Serving subsystem: dynamic-batching inference on the trained model.

The first non-training subsystem in the codebase (ROADMAP north star:
"serves heavy traffic from millions of users"). Two layers:

* :mod:`tpu_syncbn.serve.engine` — :class:`InferenceEngine`: params
  restored out of their training layout once (ZeRO flat shards gathered
  via ``parallel.zero.unshard_params``, then re-replicated), model
  pinned in eval mode (BN on running stats — collective-free, hence
  embarrassingly data-parallel), and a FIFO-bounded set of bucketed
  AOT-compiled eval programs sharded over the ``data`` axis.
* :mod:`tpu_syncbn.serve.batcher` — :class:`DynamicBatcher`: bounded
  request queue with a ``max_batch``/``max_wait_ms`` admission policy,
  pad-to-bucket coalescing, queue-full rejection (backpressure), and
  graceful drain wired to the resilience layer's
  :class:`~tpu_syncbn.runtime.resilience.PreemptionGuard`.
* :mod:`tpu_syncbn.serve.admission` — the overload-robustness layer:
  request deadlines with earliest-deadline-first dispatch and
  predicted-completion load shedding (:class:`AdmissionController`,
  :class:`LatencyEstimator`), plus a consecutive-failure
  :class:`CircuitBreaker` with PR 1 deterministic-jitter backoff
  half-open probes (docs/RESILIENCE.md "Serving failure modes").
* :mod:`tpu_syncbn.serve.loadgen` — open-loop Poisson/trace-driven
  load generation (:class:`OpenLoopLoadGen`): the offered-load sweep
  that shows graceful degradation past saturation (bounded p99, rising
  sheds — never queueing collapse).
* :mod:`tpu_syncbn.serve.publish` — zero-downtime weight publication:
  :class:`SwapController` hot-swaps manifest-verified published
  versions (or a live trainer's params, re-sharded on the mesh via
  ``parallel.redistribute``) into a running engine with drain,
  memwatch-bounded double-buffering, and automatic rollback
  (docs/RESILIENCE.md "Zero-downtime publication").

Quickstart::

    from tpu_syncbn import serve

    engine = serve.InferenceEngine.from_trainer(dp, buckets=(8, 32, 128))
    engine.warm(example_batch)                     # AOT-compile buckets
    with serve.DynamicBatcher(engine, max_batch=128,
                              max_wait_ms=5) as batcher:
        fut = batcher.submit(x[i:i + 1])           # per-request future
        logits = fut.result()

docs/PERFORMANCE.md "Serving" says how to drive this stack with
``serve.loadgen``; docs/OBSERVABILITY.md has the ``serve.*`` metric
schemas.
"""

from tpu_syncbn.parallel.zero import unshard_params  # noqa: F401
from tpu_syncbn.serve.admission import (  # noqa: F401
    AdmissionController,
    CircuitBreaker,
    CircuitOpenError,
    DeadlineExceededError,
    LatencyEstimator,
    RejectedError,
)
from tpu_syncbn.serve.batcher import DynamicBatcher  # noqa: F401
from tpu_syncbn.serve.engine import (  # noqa: F401
    InferenceEngine,
    VersionSkewError,
)
from tpu_syncbn.serve.publish import (  # noqa: F401
    PublicationError,
    SwapAbortedError,
    SwapController,
)
from tpu_syncbn.serve.loadgen import (  # noqa: F401
    LoadReport,
    OpenLoopLoadGen,
    poisson_arrivals,
    trace_arrivals,
)

__all__ = [
    "InferenceEngine",
    "DynamicBatcher",
    "RejectedError",
    "DeadlineExceededError",
    "CircuitOpenError",
    "CircuitBreaker",
    "AdmissionController",
    "LatencyEstimator",
    "OpenLoopLoadGen",
    "LoadReport",
    "poisson_arrivals",
    "trace_arrivals",
    "unshard_params",
    "SwapController",
    "PublicationError",
    "SwapAbortedError",
    "VersionSkewError",
]
