"""Online query serving: dynamic micro-batching over a resident index.

The reference engine serves its ~120 expressions from resident Spark
executors; this package is the TPU-native analog for the request-facing
path — many small concurrent point-in-polygon queries coalesced into
padded, shape-bucketed device dispatches on the module-level jitted
join, with admission control in front and the PR-1..3 resilience stack
(watchdog, retry, degradation, quarantine, fault injection) underneath.

    from mosaic_tpu.serve import ServeEngine

    engine = ServeEngine(chip_index, h3, resolution=9, bounds=bbox)
    engine.warmup()                 # precompile every bucket
    fut = engine.submit(points)     # -> concurrent.futures.Future
    rows = fut.result(timeout=1.0)  # (n,) int32, -1 = no polygon

KNN-as-a-service rides the same queue: ``engine = ServeEngine(...,
knn=build_knn_index(...))`` lets ``engine.submit_knn(points, k)``
co-batch k-nearest-neighbour requests with PIP traffic under one
admission/deadline/shed budget (`mosaic_tpu/knn` owns the bucketed
ring-expansion frontend and its Voronoi convex fast path).

Component map: `bucket.py` (pad-to-bucket ladder + compile accounting),
`admission.py` (bounded queue, deadlines, poison parking, typed
``Overloaded``), `batcher.py` (max-batch/max-wait coalescing with
per-request deadline shedding), `engine.py` (lifecycle + resilience
wiring), `router.py` (multi-tenant front door: per-tenant engines with
hard isolation, bounded residency with occupancy-aware LRU eviction,
per-tenant tuning profiles and shed accounting). Measured on the chip
by the benchmark's `taxi.serve` cell (`benchmark/run.py`).
"""

from .admission import AdmissionController, Request
from .batcher import MicroBatcher
from .bucket import BucketLadder, backend_compiles, dispatch_signature
from .engine import ServeEngine
from .router import ServeRouter, resolve_max_resident

__all__ = [
    "AdmissionController",
    "BucketLadder",
    "MicroBatcher",
    "Request",
    "ServeEngine",
    "ServeRouter",
    "backend_compiles",
    "dispatch_signature",
    "resolve_max_resident",
]
