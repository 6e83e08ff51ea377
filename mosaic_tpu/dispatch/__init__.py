"""`mosaic_tpu.dispatch` — the unified execution core.

One compile-cache/execution path for every frontend (batch `pip_join`,
`StreamJoin`, `ServeEngine`, `RasterStream`, `dist_pip_join`): bucketed
shape discipline, one `(bucket, index, mesh)` compile cache with warmup,
the watchdog/retry/host-oracle-degradation wiring, and the data-parallel
sharding hook. See `dispatch/core.py` for the ownership story and
`docs/ARCHITECTURE.md` ("Dispatch core") for the per-frontend
delegation table.
"""

from .bucket import (
    DEFAULT_MAX_BUCKET,
    DEFAULT_MIN_BUCKET,
    BucketLadder,
    backend_compiles,
    compile_cache_hits,
    dispatch_signature,
    mesh_key,
)
from .core import (
    DispatchCore,
    bounded_cache,
    cache_stats,
    cache_view,
    cells_prog,
    clear_caches,
    core_for,
    data_mesh,
    guarded_call,
    jit_compact,
    jit_counts,
    jit_join,
    join_cache_view,
    probe_check_rep,
    register_cache,
    resolve_mesh,
    sharded_join_prog,
    sharded_pointwise,
    stream_programs,
)
from .pipeline import (
    PipelineStats,
    SnapshotWriter,
    execute_pipeline,
    resolve_window,
)
from .programs import (
    ProgramFingerprintMismatch,
    ProgramStore,
    ProgramStoreCorrupt,
    backend_fingerprint,
    program_key,
    resolve_program_store,
)

__all__ = [
    "BucketLadder",
    "DEFAULT_MAX_BUCKET",
    "DEFAULT_MIN_BUCKET",
    "DispatchCore",
    "PipelineStats",
    "ProgramFingerprintMismatch",
    "ProgramStore",
    "ProgramStoreCorrupt",
    "SnapshotWriter",
    "backend_compiles",
    "backend_fingerprint",
    "bounded_cache",
    "cache_stats",
    "cache_view",
    "cells_prog",
    "clear_caches",
    "compile_cache_hits",
    "core_for",
    "data_mesh",
    "dispatch_signature",
    "execute_pipeline",
    "guarded_call",
    "jit_compact",
    "jit_counts",
    "jit_join",
    "join_cache_view",
    "mesh_key",
    "probe_check_rep",
    "program_key",
    "register_cache",
    "resolve_mesh",
    "resolve_program_store",
    "resolve_window",
    "sharded_join_prog",
    "sharded_pointwise",
    "stream_programs",
]
