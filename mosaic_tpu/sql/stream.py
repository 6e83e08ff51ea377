"""Streaming join pipeline: HBM-resident batch ring + double-buffered
cell-assignment prefetch.

Why this layer exists (round-5 chip measurement, 2026-07-31): the
1B-point device-gen stream sustained 47.2M pts/s against a 132.2M pts/s
single-batch rate (0.357x) because the `fori_loop` folded point
*generation* into every iteration and nothing overlapped batch staging
with the join. The 3DPipe lesson (PAPERS.md) is that the fix is
structural: split the stream into pipelined stages and keep the next
batch's inputs resident before the current batch's compute needs them.

Three pieces, all CPU-testable and bit-identical to the per-batch path:

- **Ring** — K pre-generated point batches stacked into one (K, B, 2)
  HBM-resident array the loop cycles (`ring_from_host` /
  `ring_from_generator`). Generator cost moves OUT of the measured loop;
  `generator_rate` (an identical fori_loop running only `gen_batch`)
  prices it separately.
- **Prefetch** — inside the jitted scan, iteration i joins batch i with
  the cell ids computed in iteration i-1 and computes batch i+1's cell
  assignment in the same program. The two stages have no data dependency,
  so XLA overlaps the cell pipeline (one-hot MXU work) with the PIP
  probe's gather/scatter phases instead of serializing them. A dispatch
  of ``nb`` batches runs ``nb`` assignments: one before the scan and one
  in every iteration but the last.
- **Accounting** — every stage emits a `stream_stage` telemetry event
  (`runtime/telemetry.py`) with measured wall seconds, and
  :func:`hbm_peak` reports the loop's high-water device memory — from
  runtime memory stats when the backend exposes them, else a live-buffer
  census (that round-5 run recorded ``peak_hbm_bytes: 0`` from a backend
  that reported no stats; that zero is the bug this closes).

Completion is always forced by :func:`fold_stats` — a device-side
(checksum, matches, overflow) fold so no per-point data crosses the
host link inside a measured region.

Durability layer (PR 3): the same stage boundaries that made the ring
fast make it checkpointable. :meth:`StreamJoin.run_durable` runs the
scan in segments of ``snapshot_every`` ring cycles, snapshotting the
scan carry (fold accumulators, ring cursor, prefetched cell ids,
optional generator key) to a checksummed run directory
(`runtime/checkpoint.py`) between segments; :meth:`StreamJoin.resume`
restarts from the last valid snapshot and converges to the SAME final
(checksum, matches, overflow) as an uninterrupted run — int32 fold
addition is exact and associative across segment boundaries, and cell
assignment is deterministic, so segmenting changes scheduling, never
values (pinned by tests/test_stream_faults.py). Every blocking device
operation sits under a `runtime/watchdog.py` deadline
(``MOSAIC_WATCHDOG_*``) with transient retry — composed by
`dispatch.guarded_call` at the ``stream.prefetch`` / ``stream.scan_step``
/ ``stream.snapshot`` sites — segment failures past the retry budget
degrade to the f64 host oracle (surfaced as ``metrics["degraded"]``,
never vanishing into the fold), and :meth:`StreamJoin.admit` diverts
poisoned input rows (NaN/Inf, out-of-CRS-bounds) into a quarantine
buffer (`runtime/quarantine.py`) instead of the device fold.

Dispatch-core unification (this PR's lane): the compiled program bundle
(assign / join / scan / durable-segment executables) is built by
:func:`build_stream_programs` and cached process-wide behind
`dispatch.stream_programs`, keyed on the static spec — two StreamJoins
over the same (system, resolution, caps, placement) share one set of
compiles, and `dispatch.cache_stats` audits the population. ``mesh=``
shards the scan data-parallel with the index replicated;
``donate_ring=True`` donates the HBM ring to the loop.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import os
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from ..dispatch import core as _dispatch, pipeline as _pipeline
from ..obs import metrics as _metrics, stages as _stages, trace as _trace
from ..tune import resolve as _tune_resolve
from ..runtime import (
    checkpoint as _checkpoint,
    faults as _faults,
    quarantine as _quarantine,
    telemetry as _telemetry,
)
from ..runtime.errors import EpochFingerprintMismatch, RetryExhausted
from .join import (
    ChipIndex,
    host_join_with_cells,
    pip_join_points,
    pip_join_points_heavy,
    resolve_probe_mode,
    tier1_compacts,
    tier2_compacted,
)


def fold_stats(out: jax.Array) -> jax.Array:
    """(3,) int32 device-side completion fold of a join output: full-bit
    XOR-shift checksum (every result bit stays live — a masked sum lets
    XLA dead-code the high half), match count, overflow count."""
    return jnp.stack(
        [
            (out ^ (out >> 16)).sum().astype(jnp.int32),
            (out >= 0).sum().astype(jnp.int32),
            (out == -2).sum().astype(jnp.int32),
        ]
    )


#: the stream assigns cells in f32 while one f32 ulp of the layer's
#: coordinates stays under this share of the resolution's cell radius
#: (`IndexSystem.buffer_radius`). Counted on 400,000 uniform points over the
#: NYC box (|lon| ~ 74, ulp 7.6e-6 deg): the f32 cell differs from the f64
#: cell on 0.14% of points at H3 res 9 (share 3.8e-3), 0.96% at res 11
#: (2.7e-2), 2.6% at res 12 (7.1e-2) — 0.37 of the share, so the threshold
#: admits under 0.2% of points moved to a neighbour cell, where they are
#: tested against that cell's chips and mostly come out unmatched
CELL_F32_MAX_ULP_SHARE = 5e-3


def stream_cell_dtype(index: ChipIndex, index_system, resolution: int):
    """The dtype a stream assigns cells in: the one rule, from static
    facts of the index (where its cells lie) and the resolution, in the
    manner of `tier1_compacts`. f32 where its rounding moves few points
    across a cell edge (`CELL_F32_MAX_ULP_SHARE`), f64 (emulated on the
    TPU, ~46 bits) where the cells are too small for the coordinates'
    magnitude: an H3 res-9 layer over New York keeps f32, a res-11 one
    does not."""
    cells = np.asarray(index.cells[:: max(index.num_cells - 1, 1)])
    centres = np.asarray(index_system.cell_center(cells), dtype=np.float64)
    ulp = float(np.spacing(np.float32(np.abs(centres).max())))
    share = ulp / float(index_system.buffer_radius(resolution))
    return jnp.float32 if share <= CELL_F32_MAX_ULP_SHARE else jnp.float64


def _ring_fingerprint(ring) -> "tuple[np.ndarray, str]":
    """The ring's host twin and its SHA-256 (`runtime/checkpoint.py`
    ``fingerprint``): every ring hash of this module, under one
    ``stream.fingerprint`` span — the pull of the whole ring to the host
    and the hash are seconds at a deployment's ring size."""
    with _trace.span("stream.fingerprint", nbytes=int(ring.nbytes)):
        ring_np = np.asarray(ring)
        return ring_np, _checkpoint.fingerprint(ring_np)


def ring_from_host(batches) -> jax.Array:
    """Stack host point batches into one (K, B, 2) f64 device-resident
    ring. Blocks until the ring is staged (staging is not loop time).
    ``stream.prefetch`` is the fault/watchdog site: staging the next
    inputs is where a transfer failure or hang surfaces in ring rebuilds."""
    with _trace.span(
        "stream.ring_build", source="host"
    ) as sp, _telemetry.timed(
        "stream_stage", stage="ring_build", source="host"
    ):

        def stage():
            ring = jnp.stack(
                [jnp.asarray(b, dtype=jnp.float64) for b in batches]
            )
            ring.block_until_ready()
            return ring

        # watchdog only — ring staging has no retry budget of its own;
        # the caller owns rebuild-vs-fail
        ring = _dispatch.guarded_call(
            "stream.prefetch", stage, retry=False
        )
        sp.set(nbytes=int(getattr(ring, "nbytes", 0)))
        return ring


def ring_from_generator(gen, key: jax.Array, k: int) -> jax.Array:
    """Device-generated ring: ``gen(fold_in(key, i)) -> (B, 2)`` for K
    distinct slots, stacked resident in HBM."""
    with _trace.span(
        "stream.ring_build", source="device_gen", k=k
    ) as sp, _telemetry.timed(
        "stream_stage", stage="ring_build", source="device_gen", k=k
    ):

        def stage():
            ring = jnp.stack(
                [gen(jax.random.fold_in(key, i)) for i in range(k)]
            )
            ring.block_until_ready()
            return ring

        ring = _dispatch.guarded_call(
            "stream.prefetch", stage, retry=False
        )
        sp.set(nbytes=int(getattr(ring, "nbytes", 0)))
        return ring


def hbm_peak(device=None) -> tuple[int, str]:
    """(peak_bytes, source) for ``device`` (default: first device).

    Prefers the runtime's ``memory_stats()`` high-water mark; a backend
    that reports none (XLA:CPU returns ``None``) falls back to a census
    of live device buffers (ring + index + loop carries are resident at
    the high-water point, so this lower-bounds the true peak).
    """
    dev = device if device is not None else jax.devices()[0]
    st = dev.memory_stats() or {}
    for key in ("peak_bytes_in_use", "bytes_in_use", "bytes_used"):
        v = int(st.get(key, 0) or 0)
        if v > 0:
            _metrics.gauge("stream.hbm_peak_bytes").set(
                v, source=f"memory_stats.{key}"
            )
            return v, f"memory_stats.{key}"
    total = sum(int(a.nbytes) for a in jax.live_arrays())
    _metrics.gauge("stream.hbm_peak_bytes").set(
        total, source="live_buffer_census"
    )
    return total, "live_buffer_census"


@dataclasses.dataclass
class StreamResult:
    """One streamed run: device-fold stats + wall-clock accounting.

    ``metrics`` is the durability/quality side channel: ``degraded``
    (any segment answered by the host oracle), ``degraded_segments``,
    ``snapshots`` written, ``resumed_from`` (ring cursor a resume
    started at, else None), and the quarantine counters when admission
    ran (``quarantined``, ``quarantine_reasons``). Plain runs carry an
    empty dict — absence of a key is never a signal.
    """

    checksum: int
    matches: int
    overflow: int
    n_points: int
    n_batches: int
    batch: int
    wall_s: float
    points_per_sec: float
    prefetch: bool
    outs: np.ndarray | None = None  # (nb, B) per-batch rows (collect=True)
    metrics: dict = dataclasses.field(default_factory=dict)


@contextlib.contextmanager
def _quiet_donation():
    """Suppress the backend's not-donatable warning: on CPU donation is
    a silent no-op by design (the bench records whether it applied via
    ``ring.is_deleted()``), and the warning would fire once per run."""
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable"
        )
        yield


@dataclasses.dataclass(frozen=True)
class StreamPrograms:
    """The compiled-executable bundle behind one StreamJoin spec.

    Built by :func:`build_stream_programs` and cached process-wide by
    `dispatch.stream_programs` — two StreamJoins over the same (system,
    resolution, caps, placement) spec replay one compiled scan instead
    of tracing their own. Every callable takes the ChipIndex as an
    argument, so the bundle is index-agnostic (the compile signature is
    the spec, not the data)."""

    assign_eager: object  #: un-jitted assign for tiny host-side lookups
    assign: object  #: jitted cell assignment (pts) -> int64 cells
    join: object  #: jitted probe (pts, cells, index) -> rows
    step: object  #: fused assign+join (pts, index) -> rows
    step_stats: object  #: fused step, device-folded to (3,) stats
    loop: object  #: jitted scan (ring, index, nb=, collect=)
    donate_loop: object  #: ring-donating twin of ``loop`` (or None)
    seg_loop: object  #: durable-segment scan (absolute batch indices)


def build_stream_programs(
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
) -> StreamPrograms:
    """Trace the full StreamJoin program set for one static spec.

    Called through the bounded `dispatch.stream_programs` cache — never
    directly. ``mesh`` (a 1-D ``dp`` mesh, or None) shards the probe
    data-parallel with the ChipIndex replicated inside the scan body;
    because each per-point result depends only on that point and the
    replicated index, the sharded scan is bit-identical to the
    single-device one. ``donate_ring`` additionally traces a donating
    twin of the scan (``donate_argnums`` on the ring) so a sustained run
    can release the K×B×2 HBM ring buffer to XLA instead of holding a
    second copy across the loop — the donating twin is a separate
    executable because warmup must not consume the caller's ring.
    """

    def assign(pts):
        with jax.named_scope("pip.cells"):
            c = index_system.point_to_cell(pts.astype(cell_dtype), resolution)
            return c.astype(jnp.int64)

    def recentred(join_points):
        def joined(pts, cells, chip_index):
            with jax.named_scope("pip.recentre"):
                shifted = (pts - chip_index.border.shift).astype(dtype)
            return join_points(
                shifted,
                cells,
                chip_index,
                heavy_cap=heavy_cap,
                found_cap=found_cap,
                probe=probe,
                convex_cap=convex_cap,
            )

        return joined

    join_one = recentred(pip_join_points)
    # the rows and the mask of rows whose cell is heavy (H > 0 only)
    join_heavy = recentred(pip_join_points_heavy)

    if mesh is None:
        join = join_one
    else:
        join = _dispatch.sharded_pointwise(
            join_one, mesh, check_rep=_dispatch.probe_check_rep(probe)
        )
        join_heavy = _dispatch.sharded_pointwise(
            join_heavy, mesh, check_rep=_dispatch.probe_check_rep(probe)
        )

    def fold(acc, out, heavy=None):
        with jax.named_scope("stream.fold"):
            if heavy is None:
                return acc + fold_stats(out)
            return acc + jnp.concatenate(
                [fold_stats(out), heavy.sum(dtype=jnp.int32)[None]]
            )

    def loop(ring, chip_index, nb: int, collect: bool):
        """One dispatch: batches [0, nb) of the ring, slot ``i % k``,
        joined and folded in one scan. It assigns cells once a batch:
        under ``prefetch`` batch 0's before the scan and batch i + 1's in
        iteration i, guarded on ``i + 1 < nb`` (``nb`` is static); without
        it batch i's in iteration i. (``seg`` takes its first cells as an
        argument and returns its last, for the snapshot, so its body's
        ``nb`` are all read.)"""
        k = ring.shape[0]
        # over an index with heavy cells the fold has a fourth entry, the
        # count of rows whose cell is heavy (`metrics["heavy_rows"]`); an
        # index without them keeps its three, and not one instruction more
        counted = bool(chip_index.heavy_edges.shape[0])

        def slot(i):
            with jax.named_scope("stream.slot"):
                return jax.lax.dynamic_index_in_dim(
                    ring, i % k, axis=0, keepdims=False
                )

        def joined(pts, cells):
            if counted:
                return join_heavy(pts, cells, chip_index)
            return join(pts, cells, chip_index), None

        acc0 = jnp.zeros(4 if counted else 3, jnp.int32)
        if prefetch:

            def body(carry, i):
                acc, cells_cur = carry
                # join batch i against the cells prefetched at i-1;
                # assign batch i+1's cells in the SAME program so XLA
                # overlaps the cell pipeline with the probe
                out, heavy = joined(slot(i), cells_cur)
                # the last iteration prefetches nothing: batch nb is no
                # part of this dispatch, and XLA cannot drop an assignment
                # whose carry every other iteration reads
                cells_next = jax.lax.cond(
                    i + 1 < nb,
                    lambda: assign(slot(i + 1)),
                    lambda: cells_cur,
                )
                return (fold(acc, out, heavy), cells_next), (
                    out if collect else None
                )

            carry0 = (acc0, assign(ring[0]))
        else:

            def body(carry, i):
                pts = slot(i)
                out, heavy = joined(pts, assign(pts))
                return fold(carry, out, heavy), (out if collect else None)

            carry0 = acc0
        carry, outs = jax.lax.scan(
            body, carry0, jnp.arange(nb, dtype=jnp.int32)
        )
        acc = carry[0] if prefetch else carry
        return acc, outs

    def seg(ring, chip_index, i0, acc, cells, nb: int, collect: bool):
        """One durable segment: the SAME scan body as ``loop`` over
        absolute batch indices [i0, i0+nb). The carry crosses segments
        through the host (snapshot), so the fold stays int32-add-exact
        and cell prefetch deterministic — segmenting is invisible in
        the final stats."""
        k = ring.shape[0]

        def slot(i):
            with jax.named_scope("stream.slot"):
                return jax.lax.dynamic_index_in_dim(
                    ring, i % k, axis=0, keepdims=False
                )

        steps = i0 + jnp.arange(nb, dtype=jnp.int32)
        if prefetch:

            def body(carry, i):
                a, cells_cur = carry
                out = join(slot(i), cells_cur, chip_index)
                cells_next = assign(slot(i + 1))
                return (fold(a, out), cells_next), (
                    out if collect else None
                )

            (acc, cells), outs = jax.lax.scan(body, (acc, cells), steps)
        else:

            def body(a, i):
                pts = slot(i)
                out = join(pts, assign(pts), chip_index)
                return fold(a, out), (out if collect else None)

            acc, outs = jax.lax.scan(body, acc, steps)
        return acc, cells, outs

    return StreamPrograms(
        assign_eager=assign,
        assign=jax.jit(assign),
        join=jax.jit(join),
        step=jax.jit(lambda pts, ix: join(pts, assign(pts), ix)),
        # fused step + fold: benches time THIS (one (3,) pull forces
        # completion; pulling the (N,) rows would measure the D2H copy)
        step_stats=jax.jit(
            lambda pts, ix: fold_stats(join(pts, assign(pts), ix))
        ),
        loop=jax.jit(loop, static_argnames=("nb", "collect")),
        donate_loop=(
            jax.jit(
                loop, static_argnames=("nb", "collect"), donate_argnums=(0,)
            )
            if donate_ring
            else None
        ),
        seg_loop=jax.jit(seg, static_argnames=("nb", "collect")),
    )


class StreamJoin:
    """Compiled streaming pip-join over a resident ring.

    Splits the fused bench step into its two stages — ``assign`` (grid
    cell ids) and ``join`` (the PIP probe) — and compiles one scan that
    cycles ring slots with optional double-buffered prefetch of the next
    batch's cell assignment. ``run`` (prefetch on) is bit-identical to
    ``run_batched`` (one call per batch, no pipeline): cell assignment is
    deterministic, so joining batch i against cells computed one
    iteration early changes scheduling, never values — pinned by
    tests/test_stream.py.

    The executables come from the unified dispatch core
    (`dispatch.stream_programs`): one traced program bundle per static
    spec, shared across StreamJoin instances and audited by
    `dispatch.cache_stats`. ``mesh=`` (or the ``MOSAIC_MESH`` knob)
    shards the probe data-parallel over a 1-D device mesh inside the
    scan with the ChipIndex replicated — bit-identical at any device
    count; the batch size must divide over the mesh. ``donate_ring=True``
    lets ``run`` donate the ring buffer to the loop (``metrics
    ["ring_donated"]`` reports whether the backend applied it — CPU
    declines donation and keeps the copy).
    """

    # constants, read only by the benchmark's `stream_ready` line
    # (benchmark/traffic_kinds/device_ring_stream.py); ROADMAP D15
    lookup = "gather"
    compaction = "scatter"

    def __init__(
        self,
        index: ChipIndex,
        index_system,
        resolution: int,
        *,
        found_cap: int | None = None,
        heavy_cap: int | None = None,
        cell_dtype=None,
        prefetch: bool = True,
        probe: "str | None" = None,
        convex_cap: int | None = None,
        donate_ring: bool = False,
        mesh=None,
        profile=None,
    ):
        self.index = index
        self.index_system = index_system
        self.resolution = resolution
        self.prefetch = bool(prefetch)
        self.donate_ring = bool(donate_ring)
        #: the TuningProfile consulted again at run_durable/resume time
        #: for the pipeline/window knobs (same precedence as here)
        self._profile = profile
        # profile-consumed knobs fold at this host entry point: explicit
        # arg > env knob > profile > built-in default (tune/resolve.py)
        knobs = _tune_resolve.resolve_knobs(
            "stream_join", profile,
            explicit={"probe": probe},
            defaults={"probe": "scatter"},
        )
        probe = knobs["probe"]
        #: (ring fingerprint, report) of the last admission, if any
        self._last_quarantine: tuple | None = None
        dtype = index.border.verts.dtype
        self.found_cap, self.heavy_cap = found_cap, heavy_cap
        # resolve the adaptive/force-lane and mesh knobs HERE, before
        # the values are closed over by the jitted scan (env changes
        # cannot reach a compiled program; see join.resolve_probe_mode)
        probe = resolve_probe_mode(probe)
        self.probe, self.convex_cap = probe, convex_cap
        self.mesh = _dispatch.resolve_mesh(mesh)
        # the cell-assignment precision: an explicit dtype wins, else the
        # rule (resolved here, like the probe: a compiled program keeps it)
        if cell_dtype is None:
            cell_dtype = stream_cell_dtype(index, index_system, resolution)
        #: the dtype cells are assigned in, by name (in every result's
        #: ``metrics`` and on the ``stream.run`` span)
        self.cell_dtype = jnp.dtype(cell_dtype).name

        progs = _dispatch.stream_programs(
            index_system, resolution, dtype=dtype, cell_dtype=cell_dtype,
            found_cap=found_cap, heavy_cap=heavy_cap, probe=probe,
            convex_cap=convex_cap, prefetch=self.prefetch,
            donate_ring=self.donate_ring, mesh=self.mesh,
        )
        self._programs = progs
        # eager twin for tiny host-side lookups (park-point search): a
        # jitted call would recompile the whole cell pipeline per shape
        self._assign_eager = progs.assign_eager
        self.assign = progs.assign
        self.join = progs.join
        self._step = progs.step
        self._step_stats = progs.step_stats
        self._loop = progs.loop
        self._donate_loop = progs.donate_loop
        self._seg_loop = progs.seg_loop
        #: (ring shape+dtype, nb, collect) signatures this instance has
        #: warmed — the jit cache itself lives on the shared program
        #: bundle, this only stops repeat warm executions per stream
        self._seg_warm: set = set()
        #: loop signatures already registered with `obs.stages`
        self._stages_seen: set = set()

    def _check_batch(self, batch: int) -> None:
        if self.mesh is not None and int(batch) % self.mesh.size:
            raise ValueError(
                f"stream batch {int(batch)} does not divide over the "
                f"{self.mesh.size}-device mesh"
            )

    def step(self, pts: jax.Array) -> jax.Array:
        """Single fused batch (assign + join) — the single-batch-rate
        reference the sustained number is measured against."""
        self._check_batch(pts.shape[0])
        return self._step(pts, self.index)

    def step_stats(self, pts: jax.Array) -> jax.Array:
        """Single fused batch, device-folded to (3,) stats."""
        self._check_batch(pts.shape[0])
        return self._step_stats(pts, self.index)

    def _register_stages(self, ring, n_batches: int, collect: bool) -> None:
        """Tell `obs.stages` how to lower the loop program again (shapes
        only, once per (ring shape, steps, collect); no lowering here)."""
        key = (tuple(ring.shape), int(n_batches), bool(collect))
        if key in self._stages_seen:
            return
        self._stages_seen.add(key)
        devices = 1 if self.mesh is None else self.mesh.size
        # the arguments as `run` and `compile` pass them: all by position
        _stages.register(
            self._donate_loop if self.donate_ring else self._loop,
            _stages.shapes_of((ring, self.index, n_batches, collect)),
            rows=int(ring.shape[1]) // devices,
        )

    def compile(self, ring: jax.Array, n_batches: int, collect=False):
        """Warm the loop program (compile time must not pollute the
        sustained measurement); emits a ``stream_stage`` compile event.
        With ``donate_ring`` the donating twin is warmed on a scratch
        copy, so the caller's ring survives warmup intact."""
        self._check_batch(ring.shape[1])
        self._register_stages(ring, n_batches, collect)
        with _telemetry.timed(
            "stream_stage", stage="compile", n_batches=n_batches,
            prefetch=self.prefetch, donate_ring=self.donate_ring,
        ):
            if self.donate_ring:
                scratch = jnp.array(ring, copy=True)
                with _quiet_donation():
                    acc, outs = self._donate_loop(
                        scratch, self.index, n_batches, collect
                    )
            else:
                acc, outs = self._loop(ring, self.index, n_batches, collect)
            jax.block_until_ready(acc)
        return acc, outs

    def run(
        self, ring: jax.Array, n_batches: int, *, collect: bool = False
    ) -> StreamResult:
        """One timed streamed pass over ``n_batches`` ring cycles.

        The whole stream is ONE dispatch (per-batch python dispatch
        measured 146 ms/batch for a 63 ms device step in r05) that
        assigns cells ``n_batches`` times, once a batch, with or without
        ``prefetch``; completion is forced by pulling the (3,) fold. With
        ``donate_ring`` the ring buffer is donated to the loop —
        ``metrics["ring_donated"]`` records whether the backend applied
        the donation (CPU declines; the ring then stays live).
        """
        k, batch = int(ring.shape[0]), int(ring.shape[1])
        self._check_batch(batch)
        donation = {}
        ring_bytes = int(ring.nbytes)  # before the loop may delete it
        with _trace.span(
            "stream.run", n_batches=n_batches, batch=batch, ring_k=k,
        ) as sp:
            self._register_stages(ring, n_batches, collect)
            t0 = time.perf_counter()
            # the launch returns once the loop is enqueued; the pull of
            # the (3,) fold is where the host waits for the device
            with _trace.span("stream.launch", n_batches=n_batches):
                if self.donate_ring:
                    with _quiet_donation():
                        acc, outs = self._donate_loop(
                            ring, self.index, n_batches, collect
                        )
                else:
                    acc, outs = self._loop(
                        ring, self.index, n_batches, collect
                    )
            with _trace.span("stream.pull"):
                acc_np = np.asarray(acc)  # the loop's only host pull
            wall = time.perf_counter() - t0
            n_points = n_batches * batch
            # the fold's fourth entry, where the index has heavy cells
            heavy_rows = int(acc_np[3]) if acc_np.shape[0] > 3 else 0
            # the join's two rules at this shard size (static facts)
            shard = batch // (1 if self.mesh is None else self.mesh.size)
            rules = {
                "compacted": tier1_compacts(
                    shard, self.found_cap, self.probe
                ),
                "tier2_compacted": tier2_compacted(
                    shard, self.index.num_heavy_cells, self.found_cap,
                    self.heavy_cap, self.probe,
                ),
            }
            sp.set(
                rows=n_points, heavy_rows=heavy_rows,
                cell_dtype=self.cell_dtype, **rules,
            )
            if self.donate_ring:
                donation = {
                    "donate_ring": True,
                    "ring_donated": bool(ring.is_deleted()),
                    "ring_bytes": ring_bytes,
                }
            _telemetry.record(
                "stream_stage", stage="join_loop",
                seconds=round(wall, 6), n_batches=n_batches, batch=batch,
                ring_k=k, prefetch=self.prefetch,
                points_per_sec=round(n_points / max(wall, 1e-9), 1),
                **donation,
            )
        return StreamResult(
            checksum=int(acc_np[0]),
            matches=int(acc_np[1]),
            overflow=int(acc_np[2]),
            n_points=n_points,
            n_batches=n_batches,
            batch=batch,
            wall_s=wall,
            points_per_sec=n_points / max(wall, 1e-9),
            prefetch=self.prefetch,
            outs=np.asarray(outs) if collect else None,
            metrics={
                **donation,
                **rules,
                "heavy_rows": heavy_rows,
                "cell_dtype": self.cell_dtype,
            },
        )

    def run_batched(self, ring: jax.Array, n_batches: int) -> StreamResult:
        """Per-batch reference path: one ``step`` call per ring slot, no
        pipeline, host-accumulated stats — the bit-identity oracle for
        the scanned loop (and the honest non-overlapped comparison)."""
        k, batch = int(ring.shape[0]), int(ring.shape[1])
        outs, acc = [], np.zeros(3, np.int64)
        t0 = time.perf_counter()
        for i in range(n_batches):
            out = self.step(ring[i % k])
            outs.append(np.asarray(out))
            acc += np.asarray(fold_stats(out), dtype=np.int64)
        wall = time.perf_counter() - t0
        n_points = n_batches * batch
        # int32 wraparound to match the device-side accumulator
        c = int(acc[0]) & 0xFFFFFFFF
        if c >= 1 << 31:
            c -= 1 << 32
        return StreamResult(
            checksum=c,
            matches=int(acc[1]),
            overflow=int(acc[2]),
            n_points=n_points,
            n_batches=n_batches,
            batch=batch,
            wall_s=wall,
            points_per_sec=n_points / max(wall, 1e-9),
            prefetch=False,
            outs=np.stack(outs),
        )

    # ------------------------------------------------------ durability

    def admit(
        self,
        batches,
        *,
        bounds: tuple | None = None,
        park: np.ndarray | None = None,
    ) -> "tuple[jax.Array, _quarantine.QuarantineReport]":
        """Validate and stage host batches into a ring; poisoned rows go
        to quarantine, never to the device fold.

        Each batch is scrubbed (`runtime/quarantine.py`): non-finite
        rows, and rows outside ``bounds`` (xmin, ymin, xmax, ymax) when
        given, are recorded in the returned
        :class:`~mosaic_tpu.runtime.quarantine.QuarantineReport` (their
        raw values land in ``report.buffer`` for triage) and replaced in
        the staged ring by the stream's *park point* — a coordinate
        proven here to hit no indexed cell, so every parked row returns
        -1 and contributes exactly zero to each fold statistic. Admitted
        rows are staged bit-identically; the ring is otherwise exactly
        :func:`ring_from_host`'s. The report's counters surface in
        ``metrics`` of subsequent :meth:`run_durable` calls.
        """
        batches = list(batches)  # materialize once (may be a generator)
        with _trace.span("stream.admit", batches=len(batches)):
            return self._admit_scrubbed(batches, bounds, park)

    def _admit_scrubbed(self, batches, bounds, park):
        raws = [
            np.asarray(
                _faults.maybe_corrupt("stream.admit", b), dtype=np.float64
            )
            for b in batches
        ]
        report = _quarantine.QuarantineReport()
        park_pt = (
            None if park is None else np.asarray(park, dtype=np.float64)
        )
        cleaned = []
        for bi, raw in enumerate(raws):
            bad, reasons = _quarantine.scrub_points(raw, bounds=bounds)
            report.merge_batch(bi, raw, bad, reasons)
            if bad.any():
                if park_pt is None:
                    park_pt = self._find_park(raws, bounds)
                clean = raw.copy()
                clean[bad] = park_pt
                cleaned.append(clean)
            else:
                cleaned.append(raw)
        ring = ring_from_host(cleaned)
        if report.n_quarantined:
            _telemetry.record("stream_quarantine", **report.metrics())
        # keyed by ring fingerprint: run_durable only surfaces this
        # report for the ring THIS admission staged, never a stale one
        self._last_quarantine = (_ring_fingerprint(ring)[1], report)
        return ring, report

    def _find_park(self, raws, bounds) -> np.ndarray:
        """The guaranteed-miss park coordinate (see ``admit``)."""
        if bounds is None:
            finite = [r[np.isfinite(r).all(axis=1)] for r in raws]
            finite = [f for f in finite if f.size]
            allp = (
                np.concatenate(finite)
                if finite
                else np.zeros((1, 2), np.float64)
            )
            bounds = (
                float(allp[:, 0].min()), float(allp[:, 1].min()),
                float(allp[:, 0].max()), float(allp[:, 1].max()),
            )
        return _quarantine.find_park_point(
            lambda p: self._assign_eager(jnp.asarray(p, jnp.float64)),
            np.asarray(self.index.cells),
            bounds,
        )

    def _warm_seg_loop(
        self, ring, cells, start_step: int, n_batches: int,
        snapshot_every: int, collect: bool,
    ) -> None:
        """Compile the durable-segment executables BEFORE the segment
        loop starts.

        Round-12 stall attribution (a CPU run; the record last stood at
        commit 76d32e0) put 1.95 s of a
        2.28 s durable run inside ``stream.segment[0]`` — almost all of
        it the seg_loop trace+compile, booked as *device* time because
        it happened under the segment span. Executing each distinct
        ``nb`` signature here (at most two: ``snapshot_every`` and the
        tail remainder; execution is required — AOT lowering does not
        populate the jit dispatch cache) moves that wall time under a
        ``dispatch.compile`` span, where timeline attribution classifies
        it as compile. Costs up to two warm segments of compute; set
        ``MOSAIC_STREAM_NO_SEG_WARMUP=1`` to skip and eat the
        segment[0] compile instead."""
        if os.environ.get("MOSAIC_STREAM_NO_SEG_WARMUP"):
            return
        sizes = sorted({
            min(snapshot_every, n_batches - s)
            for s in range(start_step, n_batches, snapshot_every)
        })
        key0 = (tuple(ring.shape), str(ring.dtype), bool(collect))
        sizes = [
            nb for nb in sizes if (key0, nb) not in self._seg_warm
        ]
        if not sizes:
            return
        c0 = _dispatch.backend_compiles()
        span = _trace.start_span(
            "dispatch.compile", site="stream.seg_loop",
            sizes=repr(sizes),
        )
        try:
            acc0 = jnp.zeros(3, jnp.int32)
            devices = 1 if self.mesh is None else self.mesh.size
            for nb in sizes:
                args = (
                    ring, self.index, jnp.int32(int(start_step)), acc0,
                    cells,
                )
                a, _c, _o = self._seg_loop(*args, nb=nb, collect=collect)
                jax.block_until_ready(a)
                self._seg_warm.add((key0, nb))
                # so that a traced durable run's device ops read by stage
                # (shapes only, as `_register_stages`; no lowering here)
                _stages.register(
                    self._seg_loop, _stages.shapes_of(args),
                    {"nb": nb, "collect": collect},
                    rows=int(ring.shape[1]) // devices,
                )
        finally:
            span.set(
                backend_compiles=_dispatch.backend_compiles() - c0
            )
            span.end()

    def _host_segment(self, ring_np, i0: int, nb: int, collect: bool):
        """f64 host-oracle evaluation of batches [i0, i0+nb) — the
        degradation fallback when a segment's device path fails past the
        retry budget. Returns ((3,) int64 fold delta, outs | None)."""
        host = self.index.host
        k = ring_np.shape[0]
        acc = np.zeros(3, np.int64)
        outs = []
        for i in range(i0, i0 + nb):
            pts = np.asarray(ring_np[i % k], np.float64)
            cells = np.asarray(
                self.index_system.point_to_cell(pts, self.resolution)
            )
            out = host_join_with_cells(pts, cells, host)
            acc += fold_stats_np(out)
            if collect:
                outs.append(out)
        return acc, (np.stack(outs) if collect else None)

    def run_durable(
        self,
        ring: jax.Array,
        n_batches: int,
        *,
        run_dir: str,
        snapshot_every: int = 8,
        collect: bool = False,
        extra_arrays: dict | None = None,
        watchdog_default_s: float = 600.0,
        retry_policy: "RetryPolicy | None" = None,
        pipeline: "bool | None" = None,
        window: "int | None" = None,
    ) -> StreamResult:
        """A streamed pass that survives device loss: the scan runs in
        segments of ``snapshot_every`` ring cycles, persisting the scan
        carry (fold accumulators, ring cursor, prefetched cell ids, any
        ``extra_arrays`` such as the generator key) to ``run_dir`` after
        each segment (`runtime/checkpoint.py`: checksummed, atomic).

        ``pipeline=True`` (default: the ``MOSAIC_STREAM_PIPELINE``
        knob) runs the segments through the asynchronous pipelined
        executor (`dispatch/pipeline.py`): the fold accumulator and
        prefetched cells stay device-resident across segments, up to
        ``window`` segments (``MOSAIC_STREAM_WINDOW``, default 4) are
        in flight at once, and snapshot I/O runs on a background writer
        thread off the device's critical path. Bit-identical to the
        synchronous loop — the carry chain is the same int32 fold — and
        the durability contract is unchanged: a snapshot is durable
        only once its background write completes, and resume replays
        from the last *completed* snapshot.

        Identical final (checksum, matches, overflow) to :meth:`run` —
        int32 fold addition segments exactly, cell prefetch is
        deterministic. Each segment dispatch sits under the
        ``stream.scan_step`` watchdog deadline and the transient-retry
        budget; past the budget the segment degrades to the f64 host
        oracle and ``metrics["degraded"]`` reports it. Snapshot failures
        never kill the run (``snapshot_skipped`` telemetry; resume
        granularity coarsens). Interrupt anywhere and
        :meth:`resume`\\ (``run_dir``, same ring) finishes the run.

        Tracing: the whole run is one ``stream.durable_run`` span with
        one child per segment and snapshot; the span's context is
        persisted in every snapshot sidecar, so a later :meth:`resume`
        JOINS the interrupted run's trace instead of starting a new one.
        The ring's pull and hash is a ``stream.fingerprint`` span
        (``nbytes``); each ``stream.snapshot`` span carries ``nbytes``
        (the carry's) and ``write_s`` (the store's share of it).
        """
        return self._run_segments(
            ring, int(n_batches), run_dir=run_dir,
            snapshot_every=int(snapshot_every), start_step=0,
            acc0=None, cells0=None, collect=collect,
            resumed_from=None, extra_arrays=extra_arrays,
            watchdog_default_s=watchdog_default_s,
            retry_policy=retry_policy,
            pipeline=pipeline, window=window,
        )

    def resume(
        self,
        run_dir: str,
        ring: jax.Array,
        *,
        collect: bool = False,
        watchdog_default_s: float = 600.0,
        retry_policy: "RetryPolicy | None" = None,
        pipeline: "bool | None" = None,
        window: "int | None" = None,
    ) -> StreamResult:
        """Restart an interrupted :meth:`run_durable` from the last
        VALID snapshot in ``run_dir`` (corrupt/truncated snapshots are
        skipped with telemetry) and run to completion.

        The snapshot's ring fingerprint, shape, and prefetch mode must
        match this stream — resuming against different data would
        silently fold garbage. Converges to the same final (checksum,
        matches, overflow) as the uninterrupted run; ``metrics
        ["resumed_from"]`` records the ring cursor resumed at. With
        ``collect=True``, ``outs`` covers only the batches run by THIS
        call (earlier rows are already folded into the snapshot).

        Tracing: the whole call is one ``stream.resume`` span with the
        children ``stream.resume.load`` (list, re-hash, ``np.load``) and
        ``stream.fingerprint`` (the ring is pulled and hashed ONCE: the
        run takes the twin), and ``ready_s``, its seconds up to the
        launch of the first resumed segment. The resumed run's
        ``stream.durable_run`` root carries ``replayed_batches``: the
        newest snapshot boundary on disk, valid or not, less
        ``resumed_from`` — 0 unless a newer snapshot was skipped.
        """
        # every snapshot of a run carries its root's trace context: the
        # span joins the interrupted run's trace before anything is loaded
        joined = _trace.SpanContext.from_dict(
            _checkpoint.newest_meta(run_dir).get("trace")
        )
        with _trace.span(
            "stream.resume", parent=joined, run_dir=run_dir
        ) as entry:
            return self._resume(
                entry, run_dir, ring, collect=collect,
                watchdog_default_s=watchdog_default_s,
                retry_policy=retry_policy, pipeline=pipeline, window=window,
            )

    def _resume(
        self, entry, run_dir, ring, *, collect, watchdog_default_s,
        retry_policy, pipeline, window,
    ) -> StreamResult:
        """:meth:`resume` under its ``stream.resume`` span, ``entry``."""
        with _trace.span("stream.resume.load"):
            loaded = _checkpoint.load_latest(run_dir)
        if loaded is None:
            raise FileNotFoundError(
                f"no valid snapshot under {run_dir!r} — nothing to resume"
            )
        step, arrays, meta = loaded
        k, batch = int(ring.shape[0]), int(ring.shape[1])
        if bool(meta.get("prefetch")) != self.prefetch:
            raise ValueError(
                f"snapshot prefetch={meta.get('prefetch')} != stream "
                f"prefetch={self.prefetch}"
            )
        if int(meta.get("ring_k", k)) != k or int(
            meta.get("batch", batch)
        ) != batch:
            raise ValueError(
                f"snapshot ring shape ({meta.get('ring_k')}, "
                f"{meta.get('batch')}) != resumed ring ({k}, {batch})"
            )
        # the ring is hashed ONCE a resume: the run below takes the twin
        ring_twin = _ring_fingerprint(ring)
        want_fp = meta.get("ring_sha256")
        if want_fp and want_fp != ring_twin[1]:
            raise ValueError(
                "snapshot ring fingerprint mismatch — this is not the "
                "ring the interrupted run was folding"
            )
        want_idx = meta.get("index_identity")
        have_idx = _checkpoint.index_identity(self.index)
        if want_idx and want_idx != have_idx:
            # the epoch-boundary refusal: a resume must finish on the
            # snapshot's epoch or not at all — folding batches joined
            # against one epoch into accumulators from another would be
            # a silent wrong answer (an epoch publish between the kill
            # and the resume is the expected way to land here)
            raise EpochFingerprintMismatch(
                f"snapshot under {run_dir!r} was taken against index "
                f"{want_idx[:24]}…, but this stream is bound to "
                f"{have_idx[:24]}… — rebuild the stream on the "
                "snapshot's epoch (EpochalIndex.replay of the matching "
                "epoch) to finish this run, or start a fresh run on "
                "the new epoch",
                expected=want_idx, actual=have_idx,
            )
        cells0 = (
            jnp.asarray(arrays["cells"]) if "cells" in arrays else None
        )
        return self._run_segments(
            ring, int(meta["n_batches"]), run_dir=run_dir,
            snapshot_every=int(meta.get("snapshot_every", 8)),
            start_step=int(step),
            acc0=np.asarray(arrays["acc"], np.int64),
            cells0=cells0, collect=collect, resumed_from=int(step),
            extra_arrays={
                key[2:]: val
                for key, val in arrays.items()
                if key.startswith("x_")
            } or None,
            watchdog_default_s=watchdog_default_s,
            retry_policy=retry_policy,
            trace_parent=_trace.SpanContext.from_dict(meta.get("trace")),
            pipeline=pipeline, window=window, ring_twin=ring_twin,
            entry_span=entry,
            # the newest boundary the interrupted run left on disk, valid
            # or not, less where this run starts: folded there, and again
            replayed_batches=max(
                _checkpoint.list_snapshots(run_dir), default=int(step)
            ) - int(step),
        )

    def _run_segments(
        self, ring, n_batches, *, run_dir, snapshot_every, start_step,
        acc0, cells0, collect, resumed_from, extra_arrays,
        watchdog_default_s, retry_policy, trace_parent=None,
        pipeline=None, window=None, ring_twin=None, entry_span=None,
        replayed_batches=None,
    ) -> StreamResult:
        k, batch = int(ring.shape[0]), int(ring.shape[1])
        self._check_batch(batch)
        snapshot_every = max(1, snapshot_every)
        # mode knobs resolved at call time, never inside traced code:
        # explicit arg > MOSAIC_STREAM_PIPELINE/_WINDOW > profile > default
        knobs = _tune_resolve.resolve_knobs(
            "stream_join.run_durable", self._profile,
            explicit={"stream_pipeline": pipeline, "stream_window": window},
            defaults={"stream_pipeline": False, "stream_window": None},
        )
        pipeline, window = knobs["stream_pipeline"], knobs["stream_window"]
        # one root span per durable run; a resume parents to the
        # INTERRUPTED run's root (persisted in the snapshot sidecars),
        # so kill + resume reads as one trace end to end
        root = _trace.start_span(
            "stream.durable_run",
            parent=trace_parent,
            n_batches=int(n_batches),
            resumed_from=resumed_from,
            snapshot_every=int(snapshot_every),
            pipelined=bool(pipeline),
        )
        if replayed_batches is not None:
            root.set(replayed_batches=int(replayed_batches))
        runner = (
            self._run_segments_pipelined if pipeline
            else self._run_segments_traced
        )
        kw = {"window": window} if pipeline else {}
        try:
            # host twin (the degradation fallback reads it) and
            # fingerprint: `resume` has hashed the ring already and hands
            # both over
            ring_np, ring_fp = ring_twin or _ring_fingerprint(ring)
            return runner(
                ring, n_batches, run_dir=run_dir,
                snapshot_every=snapshot_every, start_step=start_step,
                acc0=acc0, cells0=cells0, collect=collect,
                resumed_from=resumed_from, extra_arrays=extra_arrays,
                watchdog_default_s=watchdog_default_s,
                retry_policy=retry_policy, root=root,
                ring_np=ring_np, ring_fp=ring_fp, k=k, batch=batch,
                entry_span=entry_span, **kw,
            )
        except BaseException as e:  # noqa: BLE001 — stamped, re-raised
            root.set(error=type(e).__name__)
            raise
        finally:
            root.end()

    def _run_segments_traced(
        self, ring, n_batches, *, run_dir, snapshot_every, start_step,
        acc0, cells0, collect, resumed_from, extra_arrays,
        watchdog_default_s, retry_policy, root, ring_np, ring_fp,
        k, batch, entry_span=None,
    ) -> StreamResult:
        acc = (
            np.zeros(3, np.int64) if acc0 is None
            else _wrap_i32(np.asarray(acc0, np.int64))
        )
        if self.prefetch:
            cells = (
                cells0 if cells0 is not None
                else self.assign(ring[start_step % k])
            )
        else:
            cells = jnp.zeros((0,), jnp.int64)  # inert placeholder carry
        meta = {
            "n_batches": int(n_batches),
            "batch": batch,
            "ring_k": k,
            "prefetch": self.prefetch,
            "snapshot_every": int(snapshot_every),
            "ring_sha256": ring_fp,
            "index_identity": _checkpoint.index_identity(self.index),
            "trace": root.context.as_dict(),
        }
        degraded_segments = 0
        snapshots = 0
        outs_list: list[np.ndarray] = []
        host = getattr(self.index, "host", None)
        # compile the segment executables up front, under a compile
        # span — NOT inside segment[0]'s device-attributed wall time
        self._warm_seg_loop(
            ring, cells, start_step, int(n_batches),
            int(snapshot_every), collect,
        )
        _stamp_ready(entry_span)
        step = start_step
        t0 = time.perf_counter()
        while step < n_batches:
            seg_n = min(snapshot_every, n_batches - step)
            acc, cells, o_np, degr = self._segment_sync(
                ring, ring_np, step, seg_n, acc, cells,
                collect=collect, watchdog_default_s=watchdog_default_s,
                retry_policy=retry_policy, host=host,
            )
            degraded_segments += int(degr)
            if collect and o_np is not None:
                outs_list.append(o_np)
            step += seg_n

            with _trace.span("stream.snapshot", step=step) as ssp:
                try:
                    _dispatch.guarded_call(
                        "stream.snapshot", self._save_snapshot, ssp,
                        run_dir, step, acc, cells, extra_arrays, meta,
                        default_s=watchdog_default_s,
                        policy=retry_policy,
                    )
                    snapshots += 1
                except RetryExhausted as e:
                    # durability degrades (coarser resume point), the
                    # run itself must not die for a sick disk
                    _telemetry.record(
                        "snapshot_skipped", run_dir=run_dir, step=step,
                        error=repr(e.last)[:200],
                    )
        wall = time.perf_counter() - t0
        acc_w = _wrap_i32(acc)
        n_run = n_batches - start_step
        n_points = n_batches * batch
        _telemetry.record(
            "stream_stage", stage="durable_loop",
            seconds=round(wall, 6), n_batches=n_batches,
            batch=batch, ring_k=k, prefetch=self.prefetch,
            snapshots=snapshots, degraded_segments=degraded_segments,
            resumed_from=resumed_from,
            points_per_sec=round(
                n_run * batch / max(wall, 1e-9), 1
            ),
        )
        metrics = {
            "degraded": degraded_segments > 0,
            "degraded_segments": degraded_segments,
            "snapshots": snapshots,
            "resumed_from": resumed_from,
            "run_dir": run_dir,
        }
        if (
            self._last_quarantine is not None
            and self._last_quarantine[0] == ring_fp
        ):
            metrics.update(self._last_quarantine[1].metrics())
        return StreamResult(
            checksum=int(acc_w[0]),
            matches=int(acc_w[1]),
            overflow=int(acc_w[2]),
            n_points=n_points,
            n_batches=n_batches,
            batch=batch,
            wall_s=wall,
            points_per_sec=n_run * batch / max(wall, 1e-9),
            prefetch=self.prefetch,
            outs=(
                np.concatenate(outs_list)
                if collect and outs_list
                else None
            ),
            metrics=metrics,
        )

    def _segment_sync(
        self, ring, ring_np, step, seg_n, acc, cells, *, collect,
        watchdog_default_s, retry_policy, host,
    ):
        """One synchronous durable segment: dispatch + blocking pull
        under the ``stream.scan_step`` guard, host-oracle degradation
        past the retry budget. Returns ``(acc int64, cells, outs |
        None, degraded)``. Shared by the synchronous loop and the
        pipelined executor's transient-replay path — replay IS the
        synchronous path, so its semantics cannot drift."""
        k = int(ring.shape[0])
        acc_i32 = jnp.asarray(_wrap_i32(acc).astype(np.int32))
        cells_arg = cells

        def dispatch():
            a, c, o = self._seg_loop(
                ring, self.index, jnp.int32(step), acc_i32,
                cells_arg, nb=seg_n, collect=collect,
            )
            # one host pull forces completion (and is what a real
            # stall would block on)
            return (
                np.asarray(a), c,
                np.asarray(o) if collect else None,
            )

        with _trace.span("stream.segment", step=step, n=seg_n):
            try:
                a_np, cells_new, o_np = _dispatch.guarded_call(
                    "stream.scan_step", dispatch,
                    default_s=watchdog_default_s,
                    policy=retry_policy,
                )
                return np.asarray(a_np, np.int64), cells_new, o_np, False
            except RetryExhausted as e:
                if host is None:
                    raise
                _telemetry.record(
                    "degraded", label="stream.scan_step", step=step,
                    attempts=e.attempts, error=repr(e.last)[:200],
                )
                delta, o_np = self._host_segment(
                    ring_np, step, seg_n, collect
                )
                acc = _wrap_i32(np.asarray(acc, np.int64) + delta)
                if self.prefetch:
                    cells = self.assign(ring[(step + seg_n) % k])
                return acc, cells, o_np, True

    def _save_snapshot(
        self, span, run_dir, step, acc, cells, extra_arrays, meta
    ) -> str:
        """Pull the carry and persist it (`runtime/checkpoint.py`); the
        ``stream.snapshot`` span around the call gains ``nbytes`` (the
        carry's) and ``write_s`` (the store's share: the pull is the
        rest). Both segment loops write through here."""
        payload = self._snapshot_payload(acc, cells, extra_arrays)
        t0 = time.perf_counter()
        path = _checkpoint.save_snapshot(run_dir, step, payload, meta)
        span.set(
            nbytes=sum(int(a.nbytes) for a in payload.values()),
            write_s=round(time.perf_counter() - t0, 6),
        )
        return path

    def _snapshot_payload(self, acc, cells, extra_arrays) -> dict:
        """The snapshot carry arrays, every device pull under a
        ``dispatch.transfer.d2h`` span (``cells`` AND the ``x_<key>``
        passthroughs — timeline transfer accounting is complete)."""
        payload = {"acc": _wrap_i32(acc).astype(np.int32)}
        if self.prefetch and cells is not None:
            # a TRUE D2H interval: the segment's compute is already
            # forced complete by the acc pull, so this measures the
            # copy, not hidden device work
            with _trace.span(
                "dispatch.transfer.d2h", site="stream.snapshot",
                nbytes=int(getattr(cells, "nbytes", 0)),
            ):
                payload["cells"] = np.asarray(cells)
        for key, val in (extra_arrays or {}).items():
            with _trace.span(
                "dispatch.transfer.d2h", site="stream.snapshot",
                nbytes=int(getattr(val, "nbytes", 0)), key=key,
            ):
                payload[f"x_{key}"] = np.asarray(val)
        return payload

    def _run_segments_pipelined(
        self, ring, n_batches, *, run_dir, snapshot_every, start_step,
        acc0, cells0, collect, resumed_from, extra_arrays,
        watchdog_default_s, retry_policy, root, ring_np, ring_fp,
        k, batch, window=None, entry_span=None,
    ) -> StreamResult:
        """The asynchronous pipelined durable loop.

        Segment i+1 is dispatched while segment i still executes: the
        int32 fold accumulator and prefetched cells chain device to
        device (no per-segment host round-trip — bit-identical, the
        device fold IS the int32 wraparound `_wrap_i32` emulates), the
        blocking pull happens at the bounded window's drain, and the
        snapshot write runs on a `dispatch.pipeline.SnapshotWriter`
        thread so checkpoint I/O overlaps the next segments' compute.
        Transient failures at the drain replay through
        :meth:`_segment_sync` from the last materialized carry;
        degradation/watchdog/fault-injection semantics are the
        synchronous loop's (same ``stream.scan_step`` /
        ``stream.snapshot`` sites)."""
        acc_host = (
            np.zeros(3, np.int64) if acc0 is None
            else _wrap_i32(np.asarray(acc0, np.int64))
        )
        if self.prefetch:
            cells_dev = (
                cells0 if cells0 is not None
                else self.assign(ring[start_step % k])
            )
        else:
            cells_dev = jnp.zeros((0,), jnp.int64)  # inert placeholder
        acc_dev = jnp.asarray(_wrap_i32(acc_host).astype(np.int32))
        meta = {
            "n_batches": int(n_batches),
            "batch": batch,
            "ring_k": k,
            "prefetch": self.prefetch,
            "snapshot_every": int(snapshot_every),
            "ring_sha256": ring_fp,
            "index_identity": _checkpoint.index_identity(self.index),
            "trace": root.context.as_dict(),
        }
        degraded = [0]
        counters = {"snapshots": 0}
        outs_list: list[np.ndarray] = []
        host = getattr(self.index, "host", None)
        self._warm_seg_loop(
            ring, cells_dev, start_step, int(n_batches),
            int(snapshot_every), collect,
        )
        _stamp_ready(entry_span)
        bounds = [
            (s, min(snapshot_every, n_batches - s))
            for s in range(start_step, int(n_batches), snapshot_every)
        ]
        win = _pipeline.resolve_window(window)
        writer = _pipeline.SnapshotWriter(
            name="stream", maxsize=max(2, 2 * win)
        )
        # the replay anchor: last materialized (landed) host carry
        landed = {"acc": acc_host, "end": start_step}

        def submit_snapshot(se, acc, cells):
            def job(se=se, acc=np.asarray(acc, np.int64), cells=cells):
                with _trace.span(
                    "stream.snapshot", step=se, mode="async"
                ) as ssp:
                    try:
                        _dispatch.guarded_call(
                            "stream.snapshot", self._save_snapshot, ssp,
                            run_dir, se, acc, cells, extra_arrays, meta,
                            default_s=watchdog_default_s,
                            policy=retry_policy,
                        )
                        counters["snapshots"] += 1
                    except RetryExhausted as e:
                        _telemetry.record(
                            "snapshot_skipped", run_dir=run_dir,
                            step=se, error=repr(e.last)[:200],
                        )

            if cells is not None and hasattr(cells, "copy_to_host_async"):
                cells.copy_to_host_async()  # start the D2H now
            writer.submit(job)

        def launch(i):
            nonlocal acc_dev, cells_dev
            step, seg_n = bounds[i]
            a0, c0 = acc_dev, cells_dev

            def dispatch_async():
                # async dispatch: the returned arrays are futures; the
                # blocking pull happens at the window's drain
                return self._seg_loop(
                    ring, self.index, jnp.int32(step), a0, c0,
                    nb=seg_n, collect=collect,
                )

            with _trace.span(
                "stream.segment", step=step, n=seg_n, pipelined=True
            ):
                try:
                    a, c, o = _dispatch.guarded_call(
                        "stream.scan_step", dispatch_async,
                        default_s=watchdog_default_s,
                        policy=retry_policy,
                    )
                except RetryExhausted as e:
                    if host is None:
                        raise
                    _telemetry.record(
                        "degraded", label="stream.scan_step",
                        step=step, attempts=e.attempts,
                        error=repr(e.last)[:200],
                    )
                    # the carry chain is deterministic: pulling the
                    # in-flight acc blocks until upstream segments
                    # finish and yields the exact pre-segment fold
                    a_host = np.asarray(a0, np.int64)
                    delta, o_np = self._host_segment(
                        ring_np, step, seg_n, collect
                    )
                    acc_new = _wrap_i32(a_host + delta)
                    acc_dev = jnp.asarray(acc_new.astype(np.int32))
                    if self.prefetch:
                        cells_dev = self.assign(
                            ring[(step + seg_n) % k]
                        )
                    return ("host", acc_new, cells_dev, o_np)
                acc_dev, cells_dev = a, c
                return ("dev", a, c, o)

        def land(i, handle):
            # runs under the drain watchdog, whose deadline ABANDONS
            # the worker thread — pulls only, no state mutation (an
            # abandoned worker finishing late must change nothing)
            kind, a, c, o = handle
            if kind == "dev":
                a_np = np.asarray(a)  # blocks: the drain's one pull
                o_np = np.asarray(o) if collect else None
            else:
                a_np, o_np = a, o
            return (kind, a_np, o_np, c)

        def commit(i, pulled):
            kind, a_np, o_np, c = pulled
            step, seg_n = bounds[i]
            se = step + seg_n
            acc_w = _wrap_i32(np.asarray(a_np, np.int64))
            # submit before touching the anchor: copy_to_host_async or
            # a held writer error can raise here, and the replay must
            # then re-apply this segment from the PRE-segment carry
            submit_snapshot(se, acc_w, c if self.prefetch else None)
            if kind == "host":
                # degradation counts at materialization, not launch —
                # a degraded in-flight segment later discarded by a
                # transient is re-run (and counted once) by the replay
                degraded[0] += 1
            if collect and o_np is not None:
                outs_list.append(o_np)
            # anchor update is the final statement: nothing after the
            # submit can fail, so the anchor never runs ahead of the
            # effects it stands for
            landed["acc"] = acc_w
            landed["end"] = se

        def replay(lo, hi):
            nonlocal acc_dev, cells_dev
            acc = landed["acc"]
            step0 = bounds[lo][0]
            cells = (
                self.assign(ring[step0 % k]) if self.prefetch
                else jnp.zeros((0,), jnp.int64)
            )
            for j in range(lo, hi + 1):
                step, seg_n = bounds[j]
                acc, cells, o_np, degr = self._segment_sync(
                    ring, ring_np, step, seg_n, acc, cells,
                    collect=collect,
                    watchdog_default_s=watchdog_default_s,
                    retry_policy=retry_policy, host=host,
                )
                degraded[0] += int(degr)
                if collect and o_np is not None:
                    outs_list.append(o_np)
                landed["acc"] = _wrap_i32(np.asarray(acc, np.int64))
                landed["end"] = step + seg_n
                submit_snapshot(
                    landed["end"], landed["acc"],
                    cells if self.prefetch else None,
                )
            acc_dev = jnp.asarray(landed["acc"].astype(np.int32))
            cells_dev = cells

        t0 = time.perf_counter()
        try:
            pstats = _pipeline.execute_pipeline(
                len(bounds), launch, land,
                drain_site="stream.pipeline.drain", commit=commit,
                replay=replay, window=win,
                watchdog_default_s=watchdog_default_s,
            )
            # durability barrier: a snapshot exists only once its
            # background write completed
            with _trace.span(
                "stream.pipeline.flush", pending=writer.pending
            ), _telemetry.timed("stream_stage", stage="pipeline_flush"):
                writer.flush()
        except BaseException:
            # make completed snapshot writes durable, then let the
            # original failure win — resume replays from the last
            # COMPLETED snapshot, exactly as the synchronous loop
            with contextlib.suppress(BaseException):
                writer.close()
            raise
        writer.close()
        wall = time.perf_counter() - t0
        acc_w = _wrap_i32(landed["acc"])
        n_run = int(n_batches) - start_step
        n_points = int(n_batches) * batch
        _telemetry.record(
            "stream_stage", stage="durable_loop",
            seconds=round(wall, 6), n_batches=int(n_batches),
            batch=batch, ring_k=k, prefetch=self.prefetch,
            snapshots=counters["snapshots"],
            degraded_segments=degraded[0],
            resumed_from=resumed_from, pipelined=True,
            window=pstats.window,
            points_per_sec=round(n_run * batch / max(wall, 1e-9), 1),
        )
        metrics = {
            "degraded": degraded[0] > 0,
            "degraded_segments": degraded[0],
            "snapshots": counters["snapshots"],
            "resumed_from": resumed_from,
            "run_dir": run_dir,
            "pipeline": pstats.as_dict(),
        }
        if (
            self._last_quarantine is not None
            and self._last_quarantine[0] == ring_fp
        ):
            metrics.update(self._last_quarantine[1].metrics())
        return StreamResult(
            checksum=int(acc_w[0]),
            matches=int(acc_w[1]),
            overflow=int(acc_w[2]),
            n_points=n_points,
            n_batches=int(n_batches),
            batch=batch,
            wall_s=wall,
            points_per_sec=n_run * batch / max(wall, 1e-9),
            prefetch=self.prefetch,
            outs=(
                np.concatenate(outs_list)
                if collect and outs_list
                else None
            ),
            metrics=metrics,
        )


def _stamp_ready(entry_span) -> None:
    """``ready_s`` on the span a durable entry point opened (`resume`'s
    ``stream.resume``): its seconds up to here, where the first segment
    is about to be launched — load, ring hash, carry put and the warm
    segments are behind, the run is ahead."""
    if entry_span is not None:
        entry_span.set(ready_s=round(entry_span.elapsed(), 6))


def _wrap_i32(v: np.ndarray) -> np.ndarray:
    """int64 -> the int32 two's-complement value (the device fold's
    wraparound semantics, applied on host so segment accumulation stays
    bit-identical to one uninterrupted int32 scan)."""
    return (
        (np.asarray(v, np.int64) + (1 << 31)) % (1 << 32) - (1 << 31)
    ).astype(np.int64)


def fold_stats_np(out: np.ndarray) -> np.ndarray:
    """(3,) int64 host twin of :func:`fold_stats` (checksum term exact
    mod 2^32; wrap with :func:`_wrap_i32` after accumulating)."""
    o = np.asarray(out, np.int32)
    return np.array(
        [
            int((o ^ (o >> 16)).astype(np.int64).sum()),
            int((o >= 0).sum()),
            int((o == -2).sum()),
        ],
        dtype=np.int64,
    )


def generator_rate(
    gen, key: jax.Array, n_batches: int, batch: int
) -> tuple[float, float]:
    """(points_per_sec, wall_s) of ``gen`` alone in a fori_loop identical
    in shape to the join loop — the generator cost the r05 stream silently
    folded into its sustained number. The full-array sum keeps every
    generated element live (a partial fold would let XLA skip most of the
    generation work)."""

    @functools.partial(jax.jit, static_argnames=("nb",))
    def gen_loop(k, nb):
        def body(i, acc):
            return acc + gen(jax.random.fold_in(k, i)).sum()

        return jax.lax.fori_loop(0, nb, body, jnp.zeros((), jnp.float64))

    with _telemetry.timed(
        "stream_stage", stage="gen_compile", n_batches=n_batches
    ):
        float(gen_loop(key, n_batches))
    t0 = time.perf_counter()
    float(gen_loop(key, n_batches))
    wall = max(time.perf_counter() - t0, 1e-9)
    rate = n_batches * batch / wall
    _telemetry.record(
        "stream_stage", stage="gen_loop", seconds=round(wall, 6),
        n_batches=n_batches, batch=batch, points_per_sec=round(rate, 1),
    )
    return rate, wall
