"""Mesh-sharded point-in-polygon join with an ICI all-gathered chip index.

Reference analog: the Quickstart PIP join distributes as a Spark hash shuffle
on cell id plus an implicit broadcast of the small polygon side
(`sql/join/PointInPolygonJoin.scala:78-84`, SURVEY.md §2.8). The TPU-native
redesign keeps data resident:

- the **point side** (billions of rows) is sharded over *every* device of the
  mesh and never moves;
- the **chip index** (ChipTable compiled by `sql.join.build_chip_index`) is
  sharded over the ``cell`` mesh axis in HBM and **all-gathered over ICI**
  inside the jitted step, so each device materializes the full index exactly
  when it is needed (the BASELINE.json north-star layout);
- per-zone aggregates (the Quickstart's group-by count) are `psum`-reduced
  across the whole mesh.

No shuffle, no host round-trip: one `shard_map`-ped XLA program per step.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..core.geometry.device import DeviceGeometry
from ..dispatch import core as _dispatch
from ..runtime import faults as _faults, telemetry as _telemetry
from ..runtime.errors import DegradedResult, RetryExhausted
from ..runtime.escalate import run_escalating
from ..sql.join import (
    OVERFLOW,
    ChipIndex,
    HostRecheck,
    host_join_with_cells,
    pip_join_points,
    resolve_probe_mode,
)
from ..utils import get_logger

_I64_MAX = np.iinfo(np.int64).max


def make_mesh(
    n_devices: int | None = None,
    devices=None,
    cell_axis: int | None = None,
    slices: int | None = None,
) -> Mesh:
    """A ``(dp, cell)`` mesh — or ``(dcn, dp, cell)`` with ``slices`` set —
    over the first ``n_devices`` devices.

    Every axis shards the point axis; ``cell`` additionally shards the chip
    index (all-gathered over ICI inside the step). ``slices`` models
    multi-slice topologies: the outer ``dcn`` axis maps across slices, so
    the only cross-slice traffic is the final ``psum`` of the per-zone
    aggregates — the index all-gather stays within each slice's ICI.
    """
    devs = list(devices if devices is not None else jax.devices())
    if n_devices is not None:
        if len(devs) < n_devices:
            raise ValueError(
                f"requested {n_devices} devices but only {len(devs)} available"
            )
        devs = devs[:n_devices]
    n = len(devs)
    if cell_axis is None:
        cell_axis = 2 if n % 2 == 0 and n > 1 else 1
    if n % cell_axis:
        raise ValueError(f"{n} devices not divisible by cell_axis={cell_axis}")
    if slices is not None:
        rest = n // cell_axis
        if rest % slices:
            raise ValueError(
                f"{rest} dp-devices not divisible by slices={slices}"
            )
        return Mesh(
            np.asarray(devs).reshape(slices, rest // slices, cell_axis),
            ("dcn", "dp", "cell"),
        )
    return Mesh(np.asarray(devs).reshape(n // cell_axis, cell_axis), ("dp", "cell"))


def _round_up(n: int, m: int) -> int:
    return (n + m - 1) // m * m


def pad_index_for_shards(index: ChipIndex, shards: int) -> ChipIndex:
    """Pad the U (cells) and C (chips) axes to multiples of ``shards``.

    Pad cells are ``int64.max`` so the sorted-cells invariant that
    ``searchsorted`` relies on survives; pad chip rows have zero rings, so
    the ray-crossing test can never report them as hits.
    """
    U = int(index.cells.shape[0])
    C = int(index.chip_geom.shape[0])
    du = _round_up(U, shards) - U
    dc = _round_up(C, shards) - C
    if not du and not dc:
        return index
    b = index.border

    def pad0(x, n, value=0):
        widths = [(0, n)] + [(0, 0)] * (x.ndim - 1)
        return jnp.pad(x, widths, constant_values=value)

    return ChipIndex(
        cells=jnp.pad(index.cells, (0, du), constant_values=_I64_MAX),
        chip_rows=pad0(index.chip_rows, du, -1),
        chip_geom=jnp.pad(index.chip_geom, (0, dc)),
        chip_core=jnp.pad(index.chip_core, (0, dc)),
        border=DeviceGeometry(
            verts=pad0(b.verts, dc),
            ring_len=pad0(b.ring_len, dc),
            ring_is_hole=pad0(b.ring_is_hole, dc),
            n_rings=jnp.pad(b.n_rings, (0, dc)),
            geom_type=jnp.pad(b.geom_type, (0, dc)),
            shift=b.shift,
        ),
        # T is a power of two >= shards, so the table needs no padding; the
        # hash stays valid because its size is unchanged. The table's slots
        # index the (padded) U axis, which only grew at the end.
        hash_mult=index.hash_mult,
        table_rows=index.table_rows,
        table_cell=index.table_cell,
        table_slot=index.table_slot,
        table_pack=index.table_pack,
        cell_edges=pad0(index.cell_edges, du),
        cell_ebits=pad0(index.cell_ebits, du),
        cell_slot_geom=pad0(index.cell_slot_geom, du, -1),
        cell_slot_core=pad0(index.cell_slot_core, du),
        cell_heavy=pad0(index.cell_heavy, du, -1),
        # the heavy table is small and stays replicated — no padding needed
        heavy_edges=index.heavy_edges,
        heavy_ebits=index.heavy_ebits,
        heavy_slot_geom=index.heavy_slot_geom,
        # the per-cell route column shards with U; the tiny convex tables
        # stay replicated like the heavy ones
        cell_convex=pad0(index.cell_convex, du, -1),
        convex_edges=index.convex_edges,
        convex_ebits=index.convex_ebits,
        convex_geom=index.convex_geom,
        convex_ybin=index.convex_ybin,
    )


def _index_specs(spec, table_spec) -> ChipIndex:
    """A ChipIndex-shaped pytree of PartitionSpecs (shift stays replicated).

    ``table_spec`` covers the hash-table leaves: P(axis) when the shard
    count divides T (a power of two), P() (replicated) otherwise.
    """
    return ChipIndex(
        cells=spec,
        chip_rows=spec,
        chip_geom=spec,
        chip_core=spec,
        border=DeviceGeometry(
            verts=spec,
            ring_len=spec,
            ring_is_hole=spec,
            n_rings=spec,
            geom_type=spec,
            shift=P(),
        ),
        hash_mult=P(),
        table_rows=table_spec,
        table_cell=table_spec,
        table_slot=table_spec,
        table_pack=table_spec,
        cell_edges=spec,
        cell_ebits=spec,
        cell_slot_geom=spec,
        cell_slot_core=spec,
        cell_heavy=spec,
        heavy_edges=P(),
        heavy_ebits=P(),
        heavy_slot_geom=P(),
        cell_convex=spec,
        convex_edges=P(),
        convex_ebits=P(),
        convex_geom=P(),
        convex_ybin=P(),
    )


def _gather_index(idx: ChipIndex, axis_name: str, table_sharded: bool) -> ChipIndex:
    """All-gather the PROBE leaves of the chip index over ``axis_name``.

    Leading-axis shards were contiguous, so tiled all-gather reassembles the
    arrays in their original row order and the table's slot words stay valid.
    Leaves the probe does not read (cells/chip_rows/chip_geom/chip_core/
    border, and the host-side table_cell/table_slot/table_pack beside the
    table_rows it does) pass through sharded — no ICI traffic or replicated
    HBM is spent on them.
    """

    def g(x):
        return lax.all_gather(x, axis_name, axis=0, tiled=True)

    return dataclasses.replace(
        idx,
        table_rows=g(idx.table_rows) if table_sharded else idx.table_rows,
        cell_edges=g(idx.cell_edges),
        cell_ebits=g(idx.cell_ebits),
        cell_slot_geom=g(idx.cell_slot_geom),
        cell_slot_core=g(idx.cell_slot_core),
        cell_heavy=g(idx.cell_heavy),
        cell_convex=g(idx.cell_convex),
    )


def distributed_join_step(
    mesh: Mesh,
    num_zones: int,
    table_size: int | None = None,
    found_cap: int | None = None,
    heavy_cap: int | None = None,
    probe: str = "scatter",
    convex_cap: int | None = None,
):
    """Build the jitted full distributed join+aggregate step for ``mesh``.

    Returns ``step(points, pcells, index) -> (match, zone_counts)`` where

    - ``points``  (N, 2) shift-applied coords, N divisible by mesh size —
      sharded over ``("dp", "cell")``;
    - ``pcells``  (N,) int64 cell ids, sharded the same way;
    - ``index``   a `pad_index_for_shards(ix, mesh.shape['cell'])` chip
      index — leading axes sharded over ``"cell"``;
    - ``table_size``  T = ``index.table_cell.shape[0]``; the hash table is
      sharded over ``cell`` (and all-gathered in the step) only when the
      shard count divides T — otherwise it stays replicated, which is
      always correct (T is a power of two, so any power-of-two cell axis
      divides it; pass None to force replication);
    - ``match``   (N,) int32 matched polygon row (-1 none), sharded as input;
    - ``zone_counts`` (num_zones,) int64, globally psum-reduced (replicated);
    - ``found_cap``/``heavy_cap``/``convex_cap``  optional PER-SHARD
      compaction caps forwarded to `pip_join_points` (defaults are exact
      — no overflow);
    - ``probe``  the per-cell routing mode (see `pip_join_points`) —
      resolve it with `resolve_probe_mode` BEFORE calling if the
      force-lane env knob should apply (`dist_pip_join` does).
    """
    cell_shards = int(mesh.shape["cell"])
    table_sharded = (
        table_size is not None and cell_shards > 1 and table_size % cell_shards == 0
    )
    point_spec = P(mesh.axis_names)  # every axis shards points (dcn/dp/cell)
    index_spec = _index_specs(
        P("cell"), P("cell") if table_sharded else P()
    )

    def step(points, pcells, index):
        full = _gather_index(index, "cell", table_sharded=table_sharded)
        match = pip_join_points(
            points, pcells, full, heavy_cap=heavy_cap, found_cap=found_cap,
            probe=probe, convex_cap=convex_cap,
        )
        zone = jnp.where(match >= 0, match, num_zones).astype(jnp.int32)
        counts = jax.ops.segment_sum(
            jnp.ones_like(zone, dtype=jnp.int64), zone, num_segments=num_zones + 1
        )[:num_zones]
        counts = lax.psum(counts, mesh.axis_names)
        return match, counts

    sharded = jax.shard_map(
        step,
        mesh=mesh,
        in_specs=(point_spec, point_spec, index_spec),
        out_specs=(point_spec, P()),
        # the heavy lane's pallas_call has no shard_map replication rule
        check_vma=probe in ("scatter", "adaptive-light", "adaptive-convex"),
    )
    return jax.jit(sharded)


def pad_points(points: np.ndarray, cells: np.ndarray, multiple: int):
    """Pad the point axis to ``multiple`` with never-matching sentinels."""
    n = points.shape[0]
    d = _round_up(n, multiple) - n
    if not d:
        return points, cells
    return (
        np.pad(points, ((0, d), (0, 0))),
        np.pad(cells, (0, d), constant_values=-1),
    )


@_dispatch.bounded_cache("dist_join_step", 32)
def _cached_step(
    mesh, num_zones, table_size, found_cap, heavy_cap,
    probe="scatter", convex_cap=None,
):
    """One compiled step per (mesh, zones, layout, caps, probe) —
    escalation re-enters here with grown caps, so only distinct cap sets
    compile. Registered in the dispatch cache registry
    (`dispatch.cache_stats()["dist_join_step"]`)."""
    return distributed_join_step(
        mesh, num_zones, table_size=table_size,
        found_cap=found_cap, heavy_cap=heavy_cap,
        probe=probe, convex_cap=convex_cap,
    )


def dist_pip_join(
    points: np.ndarray,
    pcells: np.ndarray,
    index: ChipIndex,
    mesh: Mesh,
    num_zones: int,
    *,
    table_size: int | None = None,
    found_cap: int | None = None,
    heavy_cap: int | None = None,
    probe: str = "scatter",
    convex_cap: int | None = None,
    host: HostRecheck | None = None,
):
    """Managed distributed join: the resilience-wrapped spelling of
    `distributed_join_step` (the `dist_pip_join` of ISSUE/ROADMAP).

    Takes RAW (unshifted) f64 ``points`` plus their precomputed cell ids;
    owns the recenter shift, the shard padding, and the full failure
    story:

    - OVERFLOW rows (caps shrunk by `runtime.faults` injection, or
      explicit per-shard ``found_cap``/``heavy_cap`` undersized) trigger
      the bounded escalation engine — caps regrow geometrically until the
      match column is exact, else typed ``CapacityOverflow``;
    - transient device failures retry with backoff; past the budget the
      call degrades to the exact f64 host oracle (``host`` defaults to
      the index's companion) and the match column comes back flagged
      :class:`DegradedResult` — never silent ``-2``/zeroed output.

    Returns ``(match, zone_counts)``: (N,) int32 matched row per point
    and the (num_zones,) int64 per-zone histogram.
    """
    probe = resolve_probe_mode(probe)
    host = host if host is not None else getattr(index, "host", None)
    raw = np.asarray(points, dtype=np.float64)
    pc = np.asarray(pcells)
    n = raw.shape[0]
    shift = (
        host.shift
        if host is not None
        else np.asarray(index.border.shift, dtype=np.float64)
    )
    dtype = np.asarray(index.border.verts).dtype
    padded_index = pad_index_for_shards(index, int(mesh.shape["cell"]))
    p, c = pad_points((raw - shift).astype(dtype), pc, mesh.size)
    per_shard = p.shape[0] // mesh.size
    if convex_cap is None and probe != "scatter" and index.num_convex_cells:
        convex_cap = per_shard
    caps = _faults.clamp_caps(
        {
            "found_cap": found_cap,
            "heavy_cap": heavy_cap,
            "convex_cap": convex_cap if probe != "scatter" else None,
        }
    )
    grow = {k: v for k, v in caps.items() if v is not None}
    ceilings = {k: per_shard for k in grow}
    pj, cj = jnp.asarray(p), jnp.asarray(c)

    def attempt(capset):
        # fault plans for "dist_join.step" trip inside guarded_call's
        # watchdog (which evaluates maybe_fail/planned_stall pre-dispatch)
        step = _cached_step(
            mesh, num_zones, table_size,
            capset.get("found_cap"), capset.get("heavy_cap"),
            probe, capset.get("convex_cap"),
        )
        match, counts = step(pj, cj, padded_index)
        return np.asarray(match)[:n], np.asarray(counts)

    try:
        (match, counts), _ = run_escalating(
            lambda cc: _dispatch.guarded_call("dist_join.step", attempt, cc),
            grow, ceilings,
            overflow_count=lambda r: int((r[0] == OVERFLOW).sum()),
            stage="dist_pip_join",
        )
        return match, counts
    except RetryExhausted as e:
        if host is None:
            raise
        _telemetry.record(
            "degraded", label="dist_pip_join", attempts=e.attempts,
            error=repr(e.last)[:200],
        )
        get_logger("mosaic_tpu.runtime").warning(
            "dist_pip_join: device path failed %d times (%r); answering "
            "from the f64 host oracle", e.attempts, e.last,
        )
        hmatch = host_join_with_cells(raw, pc, host)
        hcounts = np.bincount(
            hmatch[hmatch >= 0], minlength=num_zones
        )[:num_zones].astype(np.int64)
        return (
            DegradedResult.wrap(
                hmatch,
                reason=f"dist_pip_join retries exhausted ({e.last!r})"[:300],
                attempts=e.attempts,
            ),
            hcounts,
        )
