"""The unified dispatch core: one compile-cache/execution path for
batch, stream, serve, and raster.

Before this package, four frontends (`sql.pip_join`, `sql.StreamJoin`,
`serve.ServeEngine`, `sql.RasterStream`) plus `parallel.dist_pip_join`
each wired their own route onto the same execution discipline: a jitted
probe behind a compile cache, a watchdog deadline, transient retry, and
f64 host-oracle degradation. The duplication was the scale blocker —
multichip sharding would have been written four times. This module owns
the discipline exactly once:

- **Shape discipline** (`.bucket`): the pad-to-bucket ladder and the
  deterministic `(bucket, index, mesh)` compile signature, lifted from
  the serving engine and now shared by every frontend.
- **Compiled programs**: the jitted join/counts/compact executables and
  the per-(system, resolution) cell-assignment programs, each behind a
  bounded, registered cache (`bounded_cache`) with one observability
  surface (:func:`cache_stats` / :func:`clear_caches`).
- **Resilience**: :func:`guarded_call` composes the watchdog deadline,
  transient retry, and degradation fallback. Frontends name their fault
  site and hand over the attempt — none re-implements the wiring.
- **Placement**: :func:`resolve_mesh` (the ``MOSAIC_MESH`` knob),
  :func:`sharded_join_prog` and :func:`sharded_pointwise` put the point
  stream data-parallel over a 1-D ``dp`` mesh with a fully replicated
  ChipIndex. Per-shard caps keep the full-bucket overflow guarantee, so
  a sharded dispatch is bit-identical to single-device by construction
  (every point's result depends only on that point and the replicated
  index).

:class:`DispatchCore` binds the pieces to one resident index: caps,
signature accounting, :meth:`~DispatchCore.warmup` precompiling every
ladder rung, and the guarded execute path. `ServeEngine` delegates to
it; `pip_join(mesh=...)` routes batches through a process-cached core
(:func:`core_for`) and thereby inherits the serving path's ~1000×
steady-state compile discipline.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..obs import metrics as _metrics
from ..obs import stages as _stages
from ..obs import trace as _trace
from ..runtime import checkpoint as _checkpoint
from ..runtime import telemetry as _telemetry, watchdog as _watchdog
from ..runtime.retry import call_with_retry
from .bucket import (
    BucketLadder,
    backend_compiles,
    dispatch_signature,
    mesh_key,
)
from .programs import (
    ProgramFingerprintMismatch,
    ProgramStoreCorrupt,
    core_program_statics,
    deserialize_compiled,
    program_key,
    resolve_program_store,
    serialize_compiled,
)

__all__ = [
    "DispatchCore",
    "bounded_cache",
    "cache_stats",
    "cache_view",
    "cells_prog",
    "clear_caches",
    "core_for",
    "data_mesh",
    "guarded_call",
    "jit_compact",
    "jit_counts",
    "jit_join",
    "join_cache_view",
    "probe_check_rep",
    "register_cache",
    "resolve_mesh",
    "sharded_join_prog",
    "sharded_pointwise",
    "stream_programs",
]


# ------------------------------------------------------------ resilience

def guarded_call(
    site: str,
    fn,
    *args,
    default_s=None,
    policy=None,
    fallback=None,
    label=None,
    classify=None,
    retry: bool = True,
    **kwargs,
):
    """THE watchdog/retry/degradation composition, written once.

    Runs ``fn(*args, **kwargs)`` under the ``site`` watchdog deadline
    (per-site ``MOSAIC_WATCHDOG_<SITE>`` beats global ``MOSAIC_WATCHDOG_S``
    beats ``default_s``; the site doubles as the fault-injection hook),
    retried on transient failures per ``policy`` (env-tuned
    ``MOSAIC_RETRY_*`` when None); past the budget it degrades through
    ``fallback`` (:class:`DegradedResult`) or raises
    :class:`RetryExhausted`. ``retry=False`` keeps only the watchdog —
    for stages whose callers own the failure (e.g. ring prefetch).

    Frontends call this instead of composing `runtime.watchdog.guard` +
    `runtime.retry.call_with_retry` themselves — the lint rule
    ``dispatch-adoption`` enforces that the wiring exists only here.
    """

    def attempt():
        return _watchdog.guard(site, fn, *args, default_s=default_s, **kwargs)

    if not retry:
        return attempt()
    kw = {"policy": policy, "fallback": fallback, "label": label or site}
    if classify is not None:
        kw["classify"] = classify
    return call_with_retry(attempt, **kw)


# ---------------------------------------------------------- cache registry

#: every compiled-program cache in the process, by name — the single
#: surface `cache_stats`/`clear_caches` (and the `unbounded-cache` lint
#: rule) audit. Values are `functools.lru_cache` wrappers or objects
#: exposing the same `cache_info()`/`cache_clear()` protocol.
_CACHES: dict = {}


def register_cache(name: str, cached_fn):
    """Register a bounded cache under the unified observability surface.
    Rejects unbounded caches — an unbounded compiled-program population
    is exactly the failure mode the bucket ladder exists to prevent."""
    info = cached_fn.cache_info()
    if info.maxsize is None:
        raise ValueError(f"dispatch cache {name!r} must be bounded")
    _CACHES[name] = cached_fn
    return cached_fn


def bounded_cache(name: str, maxsize: int):
    """Decorator: ``functools.lru_cache(maxsize)`` + registration. The
    only sanctioned way for a frontend to memoize compiled programs —
    the cache lands in :func:`cache_stats` and is bounded by
    construction."""
    if maxsize is None:
        raise ValueError("bounded_cache requires a finite maxsize")

    def deco(fn):
        return register_cache(name, functools.lru_cache(maxsize=maxsize)(fn))

    return deco


def _stats_of(cached_fn) -> dict:
    i = cached_fn.cache_info()
    out = {
        "hits": i.hits,
        "misses": i.misses,
        "maxsize": i.maxsize,
        "currsize": i.currsize,
    }
    # caches that track more than the lru_cache protocol (evictions,
    # occupancy — `_CoreCache`) surface it through the same view
    extra = getattr(cached_fn, "extra_stats", None)
    if callable(extra):
        out.update(extra())
    return out


def cache_view(name: str) -> dict:
    """`{hits, misses, maxsize, currsize}` for one registered cache
    (zeros if it was never created — nothing is cached yet)."""
    c = _CACHES.get(name)
    if c is None:
        return {"hits": 0, "misses": 0, "maxsize": 0, "currsize": 0}
    return _stats_of(c)


def cache_stats(emit: bool = True) -> dict:
    """One stats dict over EVERY dispatch-owned cache: per-cache
    ``{hits, misses, maxsize, currsize}`` plus ``jit_programs`` counting
    compiled (shape, static-args) specializations of the shared join /
    counts / compact executables. Replaces the per-frontend
    ``join_cache_stats`` / ``knn_cache_stats`` trio (kept as thin
    views). Emits one ``dispatch_cache_stats`` telemetry event
    (``emit=False`` reads silently) so long-running servers can chart
    growth and decide when to call :func:`clear_caches`."""
    stats = {name: _stats_of(c) for name, c in sorted(_CACHES.items())}
    stats["jit_programs"] = {
        "join": jit_join()._cache_size(),
        "counts": jit_counts()._cache_size(),
        "compact": jit_compact()._cache_size(),
    }
    if emit:
        _telemetry.record("dispatch_cache_stats", **stats)
    return stats


def clear_caches(names=None, emit: bool = True) -> dict:
    """Release dispatch-owned caches (all of them, or just ``names``);
    returns the pre-clear :func:`cache_stats`.

    Program caches hold strong references to every index system / mesh
    they compiled for — harmless for the built-in singletons, but a
    long-running server cycling many custom grids pins each one for
    process lifetime. This is the escape hatch: caches regrow on next
    use (the next call per shape pays one recompile). Emits
    ``dispatch_caches_cleared`` telemetry."""
    stats = cache_stats(emit=False)
    targets = (
        list(_CACHES.items())
        if names is None
        else [(n, _CACHES[n]) for n in names if n in _CACHES]
    )
    for name, c in targets:
        if name in _JIT_FACTORIES and c.cache_info().currsize:
            c().clear_cache()
        c.cache_clear()
    if emit:
        _telemetry.record("dispatch_caches_cleared", **stats)
    return stats


# ------------------------------------------------------ compiled programs

@functools.lru_cache(maxsize=1)
def _join_mod():
    # deferred: sql.join imports this package at module level, so the
    # reverse edge must resolve lazily (first program build, by which
    # point sql.join is fully initialized)
    from ..sql import join

    return join


@bounded_cache("jit_join", 1)
def jit_join():
    """The process-wide jitted exact join — ONE executable cache shared
    by batch, stream, serve, raster, and the sharded step, so a server
    and a batch job in one process share compiles. A caller that has
    probed (`pip_join`, from :func:`jit_counts`) passes ``slots=`` and
    None for the cells; the others pass the cells and the program
    probes: one cache, keyed as ever on what it is called with."""
    m = _join_mod()
    return jax.jit(
        m.pip_join_points,
        static_argnames=(
            "heavy_cap", "found_cap", "writeback", "probe", "convex_cap",
        ),
    )


@bounded_cache("jit_counts", 1)
def jit_counts():
    """The jitted count sync of `pip_join` (`sql.join._probe_counts`,
    ``probe`` static): ``(counts, slots)`` — the (3,) found / heavy /
    convex row counts, which the host pulls to size the join's caps, and
    the (N,) int32 slot column they were counted on, which stays on the
    device and is handed to :func:`jit_join` as ``slots=``, so that the
    hash table is probed once a chunk."""
    return jax.jit(_join_mod()._probe_counts, static_argnames=("probe",))


@bounded_cache("jit_compact", 1)
def jit_compact():
    """Jitted epsilon-band compaction, one compile per cap bucket."""
    return jax.jit(_join_mod()._compact, static_argnames=("cap",))


#: factories whose cached VALUE is itself a jitted wrapper — clearing
#: them must also drop the wrapper's compiled programs
_JIT_FACTORIES = frozenset({
    "jit_join", "jit_counts", "jit_compact",
    "knn_pair_distance", "knn_point_pairs", "knn_point_pairs_sharded",
})


@bounded_cache("cells_prog", 64)
def cells_prog(index_system, resolution: int, variant: str = "cells"):
    """Cached jitted cell-assignment programs per (system, res, variant).

    The lru key keeps a reference to the index system — idempotent
    systems (all built-ins) are cheap singletons, so the retention is
    harmless; :func:`clear_caches` is the escape hatch for servers
    cycling many custom grids.
    """
    assign = {
        "margin": index_system.point_to_cell_margin,
        "alt": index_system.point_to_cell_alt,
    }.get(variant, index_system.point_to_cell)

    def cells(p):
        with jax.named_scope("pip.cells"):
            return assign(p, resolution)

    return jax.jit(cells)


def join_cache_view() -> dict:
    """The legacy `sql.join.join_cache_stats` dict shape, served from
    the unified registry (`{"cells_prog": {...}, "jit_join": n,
    "jit_compact": n}`)."""
    return {
        "cells_prog": cache_view("cells_prog"),
        "jit_join": jit_join()._cache_size(),
        "jit_compact": jit_compact()._cache_size(),
    }


@bounded_cache("stream_programs", 16)
def stream_programs(
    index_system,
    resolution: int,
    *,
    dtype,
    cell_dtype,
    found_cap,
    heavy_cap,
    probe,
    convex_cap,
    prefetch,
    donate_ring,
    mesh,
):
    """The StreamJoin program bundle (assign/join/step/loop/segment
    executables) per static spec — two StreamJoins over the same
    (system, resolution, caps, placement) replay one compiled scan
    instead of tracing their own."""
    from ..sql import stream as m

    return m.build_stream_programs(
        index_system, resolution, dtype=dtype, cell_dtype=cell_dtype,
        found_cap=found_cap, heavy_cap=heavy_cap, probe=probe,
        convex_cap=convex_cap, prefetch=prefetch, donate_ring=donate_ring,
        mesh=mesh,
    )


# -------------------------------------------------------------- placement

def probe_check_rep(probe: str) -> bool:
    """shard_map replication checking must be off for lanes whose body
    contains a `pallas_call` (the heavy/adaptive tiers) — the primitive
    has no replication rule."""
    return probe in ("scatter", "adaptive-light", "adaptive-convex")


def data_mesh(n_devices: int | None = None, devices=None) -> Mesh:
    """A 1-D ``("dp",)`` data-parallel mesh over the first ``n_devices``
    devices (all of them by default) — the placement of the sharded
    dispatch lane: points sharded over ``dp``, ChipIndex replicated."""
    devs = list(devices if devices is not None else jax.devices())
    n = len(devs) if n_devices is None else int(n_devices)
    if n < 1 or n > len(devs):
        raise ValueError(
            f"mesh wants {n} devices but the platform exposes {len(devs)}"
        )
    return Mesh(np.asarray(devs[:n]), ("dp",))


def resolve_mesh(mesh):
    """Normalize a frontend ``mesh=`` argument ONCE, host-side (never at
    trace time — the compile cache keys on the resolved placement):

    - ``None`` → the ``MOSAIC_MESH`` env knob (``"4"`` or ``"dp4"`` →
      4-device data mesh; unset/empty → single-device dispatch);
    - an int → :func:`data_mesh` over that many devices;
    - a `Mesh` → used as-is (must be 1-D for the replicated-index lane).
    """
    if mesh is None:
        raw = os.environ.get("MOSAIC_MESH", "").strip().lower()
        if not raw:
            return None
        if raw.startswith("dp"):
            raw = raw[2:]
        try:
            n = int(raw)
        except ValueError:
            raise ValueError(
                f"MOSAIC_MESH={raw!r}: expected a device count like '4' "
                "or 'dp4'"
            ) from None
        if n <= 1:
            return None
        return data_mesh(n)
    if isinstance(mesh, int):
        return data_mesh(mesh) if mesh > 1 else None
    return mesh


def _replicated_index_specs():
    from ..parallel.dist_join import _index_specs

    return _index_specs(P(), P())


def sharded_pointwise(fn, mesh: Mesh, *, n_out: int = 1, check_rep: bool = True):
    """Wrap a point-wise probe ``fn(points, cells, index, ...) -> out``
    in a data-parallel `shard_map`: points/cells sharded over the 1-D
    mesh, ChipIndex replicated (no all-gather — the index fits HBM; the
    cell-sharded big-index layout stays `parallel.dist_join`'s). Each
    output axis 0 is point-sharded. Because every per-point result
    depends only on that point and the replicated index, the wrapped
    program is bit-identical to single-device execution."""
    pspec = P(mesh.axis_names)
    ispec = _replicated_index_specs()
    out_specs = pspec if n_out == 1 else tuple(pspec for _ in range(n_out))
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(pspec, pspec, ispec),
        out_specs=out_specs, check_vma=check_rep,
    )


@bounded_cache("sharded_join", 32)
def sharded_join_prog(
    mesh: Mesh,
    *,
    writeback: str,
    probe: str,
    found_cap,
    heavy_cap,
    convex_cap,
):
    """One jitted sharded exact join per (mesh, static args): the
    single-device executable's multi-chip twin. Caps are PER-SHARD
    (full per-shard rows under the ladder) so overflow stays
    structurally impossible at any device count."""
    m = _join_mod()

    def step(shifted, cells, index):
        return m.pip_join_points(
            shifted, cells, index,
            heavy_cap=heavy_cap, found_cap=found_cap,
            writeback=writeback, probe=probe, convex_cap=convex_cap,
        )

    return jax.jit(sharded_pointwise(
        step, mesh, check_rep=probe_check_rep(probe),
    ))


# ---------------------------------------------------------- DispatchCore

class DispatchCore:
    """One bucketed, warmed, resilient execution path over a resident
    ChipIndex — the unit every frontend delegates to.

    Owns: the pad-to-bucket ladder, full-(per-shard-)bucket caps, the
    `(bucket, index, mesh)` signature set with cold-compile accounting,
    :meth:`warmup` precompiling every rung, and the guarded execute path
    (watchdog + retry + f64 host-oracle degradation). With ``mesh`` set,
    dispatches run data-parallel with the index replicated — results are
    bit-identical to single-device at every device count.
    """

    def __init__(
        self,
        index,
        index_system,
        resolution: int,
        *,
        ladder: BucketLadder | None = None,
        writeback: str = "scatter",
        probe: str = "scatter",
        cell_dtype=None,
        mesh=None,
        on_cold_compile=None,
        program_store=None,
    ):
        self.index = index
        self.index_system = index_system
        self.resolution = index_system.resolution_arg(resolution)
        self.ladder = ladder or BucketLadder()
        self.writeback = writeback
        # force-lane env resolution happens once, here — dispatch uses
        # the pinned value so the compile-cache signature stays honest
        self.probe = _join_mod().resolve_probe_mode(probe)
        if self.probe != "scatter" and writeback == "direct":
            raise ValueError(
                "probe='adaptive' requires writeback scatter|gather"
            )
        self.cell_dtype = cell_dtype
        self.mesh = resolve_mesh(mesh)
        if self.mesh is not None and self.ladder.min_bucket % self.mesh.size:
            raise ValueError(
                f"min_bucket {self.ladder.min_bucket} must divide evenly "
                f"over the {self.mesh.size}-device mesh"
            )
        dtype = index.border.verts.dtype
        self._dtype = dtype
        host = getattr(index, "host", None)
        self._host = host
        self._shift = (
            host.shift
            if host is not None
            else np.asarray(index.border.shift, dtype=np.float64)
        )
        self._signatures: set = set()
        self._warmed: frozenset | None = None
        self._cold_compiles = 0
        self._on_cold_compile = on_cold_compile
        # AOT program persistence (dispatch/programs.py): explicit arg
        # beats the MOSAIC_PROGRAM_STORE env knob. Sharded executables
        # bind to a concrete mesh topology the store does not model, so
        # a meshed core refuses the store (recorded, never silent).
        self._programs = resolve_program_store(program_store)
        if self._programs is not None and self.mesh is not None:
            _telemetry.record(
                "program_store_refused", reason="mesh",
                devices=self.mesh.size,
            )
            self._programs = None
        self._aot: dict = {}  # bucket -> (cells_fn, join_fn) | None
        self.aot_stats = {"loaded": 0, "exported": 0, "fallback": 0}
        #: bytes put to and pulled from the device by `execute_padded`
        #: (the ``nbytes`` of its transfer spans, summed)
        self.transfer_bytes = {"h2d": 0, "d2h": 0}

    # ------------------------------------------------------- accounting

    @property
    def signatures(self) -> set:
        return self._signatures

    @property
    def cold_compiles(self) -> int:
        return self._cold_compiles

    @property
    def warmed(self) -> bool:
        return self._warmed is not None

    def _shard_rows(self, bucket: int) -> int:
        return bucket if self.mesh is None else bucket // self.mesh.size

    def caps(self, bucket: int):
        """Full-bucket caps — PER SHARD under a mesh — so tier overflow
        is structurally impossible and the static-arg set per bucket
        never changes at runtime."""
        rows = self._shard_rows(bucket)
        fcap = None if self.writeback == "direct" else rows
        hcap = rows if self.index.num_heavy_cells else None
        ccap = (
            rows
            if self.probe != "scatter" and self.index.num_convex_cells
            else None
        )
        return fcap, hcap, ccap

    def compacted(self, bucket: int) -> bool:
        """`join.tier1_compacts` of this core's join program at
        ``bucket``: with full-bucket caps, true only of an adaptive
        probe."""
        return _join_mod().tier1_compacts(
            self._shard_rows(bucket), self.caps(bucket)[0], self.probe,
            self.writeback,
        )

    def tier2_compacted(self, bucket: int) -> bool:
        """`join.tier2_compacted` of the same program: with full-bucket
        caps, false of every probe."""
        fcap, hcap, _ = self.caps(bucket)
        return _join_mod().tier2_compacted(
            self._shard_rows(bucket), self.index.num_heavy_cells, fcap,
            hcap, self.probe, self.writeback,
        )

    def signature(self, bucket: int) -> tuple:
        fcap, hcap, ccap = self.caps(bucket)
        return dispatch_signature(
            bucket, self.index, writeback=self.writeback, found_cap=fcap,
            heavy_cap=hcap, probe=self.probe, convex_cap=ccap, mesh=self.mesh,
        )

    def freeze(self) -> None:
        """Snapshot the signature set — any later dispatch introducing a
        new signature counts as a cold compile (the bounded-compile
        contract's tripwire)."""
        self._warmed = frozenset(self._signatures)

    # ------------------------------------------------------ AOT programs

    def _index_fingerprint(self) -> str:
        """Restart-stable tessellation identity for program-store keys
        (the in-process `dispatch_signature` keys on ``id(index)``,
        which a restart recycles). Epoch-aware: an index published by
        `mosaic_tpu.index.epoch.EpochalIndex` folds its epoch token in,
        so two epochs never share a key even when their cell sets
        coincide bit-for-bit — loading a program exported against a
        superseded chip table would bind the wrong epoch."""
        if getattr(self, "_index_fp", None) is None:
            self._index_fp = _checkpoint.index_identity(self.index)
        return self._index_fp

    def _epoch_meta(self) -> dict:
        """Epoch provenance for program-store sidecars (empty for
        build-once indexes) — what `ProgramStore.gc_superseded` keys
        on to drop entries from earlier epochs of the same series."""
        series = getattr(self.index, "epoch_series", None)
        if not series:
            return {}
        return {
            "index_series": series,
            "index_epoch": int(getattr(self.index, "epoch", 0)),
        }

    def _aot_bundle(self, bucket: int):
        """The bucket's ``(cells_fn, join_fn)`` AOT pair: loaded from
        the program store when a valid entry exists, otherwise compiled
        and exported. Any refusal (corrupt entry, fingerprint mismatch,
        unserializable program) falls back to the plain jit path for
        this bucket — never a wrong program, never a crash."""
        if bucket in self._aot:
            return self._aot[bucket]
        with _trace.span("dispatch.aot", bucket=bucket):
            try:
                bundle = self._load_or_export(bucket)
            except Exception as e:  # lint: broad-except-ok (AOT is an optimization: ANY failure in serialization internals must degrade to plain compilation, not take down the dispatch)
                _telemetry.record(
                    "program_store_fallback", bucket=bucket,
                    error=repr(e)[:200],
                )
                self.aot_stats["fallback"] += 1
                bundle = None
        self._aot[bucket] = bundle
        return bundle

    def _load_or_export(self, bucket: int):
        import jax as _jax

        fp = self._index_fingerprint()
        fcap, hcap, ccap = self.caps(bucket)
        # prototypes mirror execute_padded exactly: jnp.asarray folds the
        # x64 config into the cells input dtype; shifted uses the index
        # vertex dtype
        in_dtype = (
            np.dtype(self.cell_dtype)
            if self.cell_dtype is not None
            else _jax.dtypes.canonicalize_dtype(np.float64)
        )
        pts_proto = _jax.ShapeDtypeStruct((bucket, 2), in_dtype)
        cfn = cells_prog(self.index_system, self.resolution, "cells")
        cells_aval = _jax.eval_shape(cfn, pts_proto)

        cells_fn = self._one_program(
            program_key(fp, "cells", **core_program_statics(
                self, bucket, "cells")),
            lambda: cfn.lower(pts_proto).compile(),
            (pts_proto,), cells_aval,
            meta={"kind": "cells", "bucket": bucket,
                  **self._epoch_meta()},
        )

        shifted_proto = _jax.ShapeDtypeStruct((bucket, 2), self._dtype)
        jj = jit_join()
        statics = self._join_statics(fcap, hcap, ccap)
        out_aval = _jax.eval_shape(
            lambda a, b, c: jj(a, b, c, **statics),
            shifted_proto, cells_aval, self.index,
        )
        join_fn = self._one_program(
            program_key(fp, "join", **core_program_statics(
                self, bucket, "join")),
            lambda: jj.lower(
                shifted_proto, cells_aval, self.index, **statics
            ).compile(),
            (shifted_proto, cells_aval, self.index), out_aval,
            meta={"kind": "join", "bucket": bucket,
                  **self._epoch_meta()},
        )
        return cells_fn, join_fn

    def _one_program(self, key, compile_fn, example_args, out_aval, meta):
        """Load one program from the store or compile + export it.
        Typed store refusals (corrupt, fingerprint mismatch) degrade to
        the compile path and re-export — the store self-heals."""
        payload = None
        try:
            payload = self._programs.load(key)
        except (ProgramStoreCorrupt, ProgramFingerprintMismatch):
            pass  # typed telemetry already recorded by the store
        if payload is not None:
            # the store serves single-device cores only (a meshed core
            # refuses it above), so every persisted program was compiled
            # for the device that holds the index
            fn = deserialize_compiled(
                payload, example_args, out_aval,
                devices=self.index.cells.devices(),
            )
            self.aot_stats["loaded"] += 1
            return fn
        compiled = compile_fn()
        self._programs.save(key, serialize_compiled(compiled), meta=meta)
        self.aot_stats["exported"] += 1
        return compiled

    # ---------------------------------------------------------- execute

    def execute_padded(self, padded: np.ndarray) -> np.ndarray:
        """One exact device join of a full-bucket batch (the compile
        unit warmup precompiles and dispatch replays); sharded over the
        mesh when one is bound."""
        bucket = padded.shape[0]
        if self.mesh is not None and bucket % self.mesh.size:
            raise ValueError(
                f"bucket {bucket} does not divide over the "
                f"{self.mesh.size}-device mesh"
            )
        fcap, hcap, ccap = self.caps(bucket)
        sig = self.signature(bucket)
        new_sig = sig not in self._signatures
        if new_sig:
            self._signatures.add(sig)
            if self._warmed is not None:
                self._cold_compiles += 1
                if self._on_cold_compile is not None:
                    self._on_cold_compile(bucket, len(self._signatures))
                else:
                    _telemetry.record(
                        "dispatch_compile", bucket=bucket,
                        signatures=len(self._signatures),
                    )
        # a new signature means the program calls below will lower and
        # compile: span the whole dispatch so the compile wall time gets
        # an interval (class `compile`), stamped with the real XLA meter
        # delta; warm replays skip the span entirely (no per-dispatch
        # overhead, and the timeline never mistakes replay for compile)
        comp_span = None
        comp_c0 = None
        if new_sig:
            comp_c0 = backend_compiles()
            comp_span = _trace.start_span(
                "dispatch.compile", bucket=bucket,
                signatures=len(self._signatures),
            )
        bundle = self._aot_bundle(bucket) if self._programs is not None else None
        try:
            with _trace.span(
                "dispatch.transfer.h2d", nbytes=int(padded.nbytes),
                bucket=bucket,
            ):
                dev = jnp.asarray(padded)
                if self.cell_dtype is not None:
                    dev = dev.astype(self.cell_dtype)
            self.transfer_bytes["h2d"] += int(padded.nbytes)
            # always the JITTED cell program (shared `cells_prog` lru,
            # one compile per bucket, precompiled by warmup): the
            # batch-path heuristic of going eager below 64k rows on CPU
            # trades a one-off compile for a ~1000x slower dispatch —
            # the right trade for a single cold batch, the wrong one on
            # a hot path. With a program store bound, the bucket's
            # AOT-loaded executables replace both programs outright.
            # The launch spans time the ENQUEUE, not the execution.
            with _trace.span(
                "dispatch.launch", program="cells", bucket=bucket,
            ):
                if bundle is not None:
                    cells = bundle[0](dev)
                else:
                    cells = cells_prog(
                        self.index_system, self.resolution, "cells"
                    )(dev)
            with _trace.span(
                "dispatch.transfer.h2d", nbytes=int(padded.nbytes),
                bucket=bucket, shifted=True,
            ):
                # cast host-side (IEEE round-to-nearest, bit-identical
                # to XLA's convert) so the transfer is a plain device
                # put — jnp.asarray with a dtype change would compile a
                # tiny convert program per bucket shape, which a
                # store-warmed restart counts as a cold compile
                shifted = jnp.asarray(
                    np.asarray(padded - self._shift, dtype=self._dtype)
                )
            self.transfer_bytes["h2d"] += int(shifted.nbytes)
            with _trace.span(
                "dispatch.launch", program="join", bucket=bucket,
            ):
                if bundle is not None:
                    out = bundle[1](shifted, cells, self.index)
                elif self.mesh is None:
                    out = jit_join()(
                        shifted, cells, self.index,
                        **self._join_statics(fcap, hcap, ccap),
                    )
                else:
                    out = self._sharded_prog(fcap, hcap, ccap)(
                        shifted, cells, self.index
                    )
            if new_sig and bundle is None:
                self._register_stages(bucket, dev, shifted, cells)
            # the result pull also blocks on device compute on async
            # backends, so this upper-bounds the true D2H copy — still
            # the only host-visible interval the copy has
            with _trace.span(
                "dispatch.transfer.d2h",
                nbytes=int(getattr(out, "nbytes", 0)), bucket=bucket,
            ):
                res = np.asarray(out)
            self.transfer_bytes["d2h"] += int(res.nbytes)
            return res
        finally:
            if comp_span is not None:
                c1 = backend_compiles()
                if comp_c0 is not None and c1 is not None:
                    comp_span.set(backend_compiles=c1 - comp_c0)
                comp_span.end()

    def _register_stages(self, bucket, dev, shifted, cells) -> None:
        """Tell `obs.stages` how to lower this bucket's two programs
        again (shapes only; nothing is lowered here)."""
        rows = bucket if self.mesh is None else bucket // self.mesh.size
        fcap, hcap, ccap = self.caps(bucket)
        shapes = _stages.shapes_of
        _stages.register(
            cells_prog(self.index_system, self.resolution, "cells"),
            shapes((dev,)), rows=rows,
        )
        args = shapes((shifted, cells, self.index))
        if self.mesh is None:
            _stages.register(
                jit_join(), args, self._join_statics(fcap, hcap, ccap),
                rows=rows,
            )
        else:
            _stages.register(
                self._sharded_prog(fcap, hcap, ccap), args, rows=rows
            )

    def _join_statics(self, fcap, hcap, ccap) -> dict:
        return dict(
            heavy_cap=hcap, found_cap=fcap, writeback=self.writeback,
            probe=self.probe, convex_cap=ccap,
        )

    def _sharded_prog(self, fcap, hcap, ccap):
        return sharded_join_prog(
            self.mesh, writeback=self.writeback, probe=self.probe,
            found_cap=fcap, heavy_cap=hcap, convex_cap=ccap,
        )

    def execute(self, points) -> np.ndarray:
        """Pad → dispatch → unpad (exact, unguarded)."""
        padded, n = self.ladder.pad(points)
        return self.execute_padded(padded)[:n]

    def execute_resilient(
        self, site: str, padded: np.ndarray, *,
        default_s=None, policy=None,
    ) -> np.ndarray:
        """:meth:`execute_padded` under the ``site`` watchdog deadline,
        transient retry, and exact-f64 host-oracle degradation."""
        fallback = None
        if self._host is not None:
            m = _join_mod()
            fallback = lambda: m.host_join(  # noqa: E731
                padded, self._host, self.index_system, self.resolution
            )
        return guarded_call(
            site, self.execute_padded, padded,
            default_s=default_s, policy=policy, fallback=fallback,
        )

    # ----------------------------------------------------------- warmup

    def warmup(self) -> dict:
        """Precompile every ladder bucket against the resident index
        (on the bound mesh), then freeze the signature set. Returns
        ``{"buckets", "seconds", "signatures"}`` plus the real
        ``backend_compiles`` delta when the XLA meter is available."""
        t0 = backend_compiles()
        with _telemetry.capture() as events, _trace.span(
            "dispatch.warmup", buckets=len(self.ladder.buckets),
            devices=1 if self.mesh is None else self.mesh.size,
        ):
            for b in self.ladder.buckets:
                pts = np.zeros((b, 2), dtype=np.float64)
                with _telemetry.timed(
                    "dispatch_stage", stage="warmup", bucket=b
                ):
                    self.execute_padded(pts)
        total = sum(
            e["seconds"]
            for e in events
            if e.get("stage") == "warmup" and "seconds" in e
        )
        self.freeze()
        t1 = backend_compiles()
        out = {
            "buckets": len(self.ladder.buckets),
            "seconds": round(total, 4),
            "signatures": len(self._signatures),
        }
        if t0 is not None and t1 is not None:
            out["backend_compiles"] = t1 - t0
        if self._programs is not None:
            out["aot"] = dict(self.aot_stats)
            em = self._epoch_meta()
            if em:
                # this core IS the current epoch: entries exported for
                # earlier epochs of the same series can never be loaded
                # again (the epoch token is in their key) — drop them
                # so a mutating index doesn't grow the store unbounded
                out["aot_gc"] = self._programs.gc_superseded(
                    em["index_series"], em["index_epoch"]
                )
        _telemetry.record("dispatch_warmup", **out)
        return out


# -------------------------------------------- batch-path core memoization

class _CoreCache:
    """A bounded occupancy-aware LRU cache for resident
    :class:`DispatchCore` instances, speaking the `lru_cache`
    `cache_info()`/`cache_clear()` protocol so it registers in
    :func:`cache_stats` like every other dispatch cache.

    Eviction picks the least-recently-used entry, with COLD cores
    (never warmed — no precompiled ladder, so nothing of value to
    drop) evicted before warmed ones regardless of recency: a tenant
    whose core was warmed at real compile cost outlives a tenant that
    never finished warming. Evictions and occupancy land in the
    ``extra_stats`` view (`cache_stats`/`cache_view` merge it) and on
    the obs metrics spine (``dispatch.core_cache_evictions`` counter,
    ``dispatch.core_cache_occupancy`` gauge)."""

    def __init__(self, maxsize: int):
        self.maxsize = maxsize
        self._d: dict = {}
        self._hits = 0
        self._misses = 0
        self._evictions = 0

    def get(self, key):
        core = self._d.get(key)
        if core is not None:
            self._hits += 1
            # LRU recency: a hit moves the entry to the back
            self._d[key] = self._d.pop(key)
        return core

    def _evict_one(self) -> None:
        victim = next(
            (k for k, c in self._d.items() if not getattr(c, "warmed", False)),
            next(iter(self._d)),
        )
        self._d.pop(victim)
        self._evictions += 1
        _metrics.counter(
            "dispatch.core_cache_evictions",
            "resident DispatchCores dropped by the occupancy-aware LRU",
        ).inc()

    def put(self, key, core):
        self._misses += 1
        while len(self._d) >= self.maxsize:
            self._evict_one()
        self._d[key] = core
        _metrics.gauge(
            "dispatch.core_cache_occupancy",
            "resident DispatchCore slots in use / maxsize",
        ).set(len(self._d) / max(self.maxsize, 1))

    def occupancy(self) -> float:
        return len(self._d) / max(self.maxsize, 1)

    def extra_stats(self) -> dict:
        return {
            "evictions": self._evictions,
            "occupancy": round(self.occupancy(), 4),
        }

    def cache_info(self):
        return functools._CacheInfo(
            self._hits, self._misses, self.maxsize, len(self._d)
        )

    def cache_clear(self):
        self._d.clear()
        self._hits = 0
        self._misses = 0
        self._evictions = 0


_BATCH_CORES = _CoreCache(maxsize=8)
_CACHES["batch_cores"] = _BATCH_CORES


def core_for(
    index,
    index_system,
    resolution: int,
    *,
    ladder: BucketLadder | None = None,
    writeback: str = "scatter",
    probe: str = "scatter",
    cell_dtype=None,
    mesh=None,
) -> DispatchCore:
    """The process-cached :class:`DispatchCore` for a (index, placement,
    static-args) combination — repeated `pip_join(mesh=...)` calls and
    the multichip bench reuse one warmed core instead of re-tracking
    signatures per call. The cache holds the index strongly, so the
    `id(index)` component of the key cannot be recycled while the entry
    lives."""
    mesh = resolve_mesh(mesh)
    key = (
        id(index), id(index_system), index_system.resolution_arg(resolution),
        writeback, probe, str(cell_dtype), mesh_key(mesh),
        ladder or BucketLadder(),
    )
    core = _BATCH_CORES.get(key)
    if core is None or core.index is not index:
        core = DispatchCore(
            index, index_system, resolution, ladder=ladder,
            writeback=writeback, probe=probe,
            cell_dtype=cell_dtype, mesh=mesh,
        )
        _BATCH_CORES.put(key, core)
    return core
