"""Map a `WorkloadProfile` to a `TuningProfile` with auditable rules.

PAPERS.md's *Adaptive Geospatial Joins for Modern Hardware* picks the join
strategy from measured data statistics; this module is that idea over our
knob surface. Every rule reads a statistic of the profile it is given
and nothing else: no file, no environment, no record of an earlier run.
Every recommendation carries a machine-checkable rationale entry
``{knob, value, rule, evidence}`` so a reviewer (or a test) can replay the
decision from the profile alone. A knob the rules have no evidence for
stays None, which the resolver reads as "keep the built-in default" — the
optimizer never guesses.
"""

from __future__ import annotations

import dataclasses

from ..runtime import telemetry as _telemetry
from .profiler import WorkloadProfile

#: class-share threshold above which the per-cell router is chosen: below
#: it the router's overhead is spent on cells the scatter lane serves as
#: well (a pre-chip CPU reading set it; no chip reading has moved it)
ADAPTIVE_DENSE_SHARE = 0.25

#: tile occupancy below which the tile shape is halved: sparse coverage
#: spends a big tile's compute on padding
SPARSE_TILE_OCCUPANCY = 0.5

#: border-pair share above which an overlay join is predicate-bound and
#: one step finer tessellation pays: smaller cells convert border chips
#: to core chips, and core pairs are decided WITHOUT the exact
#: ``st_intersects`` predicate (`sql/overlay.py` accepts them outright),
#: so past an even split the predicate batch shrinks faster than the
#: candidate list grows
OVERLAY_BORDER_SHARE = 0.5

#: candidate-pair count above which the device overlay lane spreads its
#: fixed costs (prep transfer + one fused launch) over enough pairs; below
#: it the host numpy twin is exact and needs neither
OVERLAY_DEVICE_CANDIDATES = 4096

#: convex-candidate share above which the KNN Voronoi fast path pays: the
#: one-shot cover dispatch needs the Voronoi walk's strict-descent
#: guarantee, which only convex chip sites give, so its fallback-to-ring
#: fraction tracks (1 - convex share); past half-convex the ring
#: iterations saved outnumber the walks wasted on the non-convex half
KNN_CONVEX_SHARE = 0.5


@dataclasses.dataclass
class TuningProfile:
    """A set of knob recommendations. None = no recommendation: the
    resolver falls through to the built-in default. ``rationale`` is the
    machine-checkable audit trail, ``source`` summarizes the inputs."""

    resolution: "int | None" = None
    probe: "str | None" = None
    writeback: "str | None" = None
    batch_size: "int | None" = None
    bucket_min: "int | None" = None
    bucket_max: "int | None" = None
    stream_window: "int | None" = None
    stream_pipeline: "bool | None" = None
    raster_tile: "tuple | None" = None
    zonal_lane: "str | None" = None
    overlay_lane: "str | None" = None
    knn_lane: "str | None" = None
    rationale: list = dataclasses.field(default_factory=list)
    source: dict = dataclasses.field(default_factory=dict)

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        if d.get("raster_tile") is not None:
            d["raster_tile"] = list(d["raster_tile"])
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "TuningProfile":
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in d.items() if k in names}
        if kw.get("raster_tile") is not None:
            kw["raster_tile"] = tuple(int(v) for v in kw["raster_tile"])
        return cls(**kw)

    @classmethod
    def merged(cls, *profiles: "TuningProfile") -> "TuningProfile":
        """Combine recommendations from complementary workload profiles
        (e.g. the polygon side's resolution with the point side's probe
        and batch knobs). First non-None wins per knob; rationales
        concatenate in the same order so the audit trail survives."""
        out = cls()
        for p in profiles:
            for f in dataclasses.fields(cls):
                if f.name in ("rationale", "source"):
                    continue
                if getattr(out, f.name) is None:
                    setattr(out, f.name, getattr(p, f.name))
            out.rationale.extend(p.rationale)
            out.source.setdefault("merged", []).append(p.source)
        return out


def _next_pow2(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def recommend(profile: WorkloadProfile) -> TuningProfile:
    """The rule table. Each branch appends one rationale entry; the
    returned profile's ``source`` echoes the statistics it read."""
    with _telemetry.timed("tune_stage", stage="recommend", kind=profile.kind):
        return _recommend(profile)


def _recommend(profile: WorkloadProfile) -> TuningProfile:
    out = TuningProfile()
    why = out.rationale

    def set_knob(knob, value, rule, evidence):
        setattr(out, knob, value)
        why.append({"knob": knob, "value": value if not isinstance(value, tuple)
                    else list(value), "rule": rule, "evidence": evidence})

    if profile.kind == "polygons" and profile.optimal_resolution is not None:
        set_knob(
            "resolution", int(profile.optimal_resolution),
            "analyzer-target-cells",
            {"cells_per_geom": profile.cells_per_geom,
             "optimal_resolution": profile.optimal_resolution},
        )

    if (
        profile.kind == "overlay"
        and profile.border_fraction is not None
        and profile.resolution is not None
        and profile.border_fraction > OVERLAY_BORDER_SHARE
    ):
        # consumed from the overlay.candidates span stats the profiler
        # captures (sql/overlay.py emits them on every candidate pass)
        set_knob(
            "resolution", int(profile.resolution) + 1,
            "border-dominated-finer-tessellation",
            {"border_fraction": profile.border_fraction,
             "sure_fraction": profile.sure_fraction,
             "candidates": profile.n_sampled,
             "threshold": OVERLAY_BORDER_SHARE},
        )

    if profile.kind == "overlay" and profile.n_sampled:
        evidence = {
            "candidates": profile.n_sampled,
            "threshold": OVERLAY_DEVICE_CANDIDATES,
        }
        if profile.n_sampled >= OVERLAY_DEVICE_CANDIDATES:
            # the fused device lane spreads its fixed prep/launch cost
            # over enough pairs
            set_knob("overlay_lane", "device",
                     "device-lane-amortized-candidates", evidence)
        else:
            set_knob("overlay_lane", "host",
                     "small-candidate-host-lane", evidence)

    shares = profile.class_shares or {}
    dense = float(shares.get("heavy", 0.0)) + float(shares.get("convex", 0.0))
    if profile.kind == "points" and shares:
        if dense > ADAPTIVE_DENSE_SHARE:
            set_knob(
                "probe", "adaptive", "dense-share-router",
                {"heavy": shares.get("heavy"), "convex": shares.get("convex"),
                 "threshold": ADAPTIVE_DENSE_SHARE},
            )
        else:
            set_knob(
                "probe", "scatter", "light-dominated-single-lane",
                {"light": shares.get("light"),
                 "threshold": ADAPTIVE_DENSE_SHARE},
            )

    if profile.kind == "points" and shares:
        convex = float(shares.get("convex", 0.0))
        evidence = {"convex": convex, "threshold": KNN_CONVEX_SHARE}
        if convex > KNN_CONVEX_SHARE:
            # mostly-convex candidates: the Voronoi walk's one-shot cover
            # replaces the iterative ring loop
            set_knob("knn_lane", "voronoi",
                     "convex-share-voronoi-lane", evidence)
        else:
            set_knob("knn_lane", "ring",
                     "mixed-share-ring-lane", evidence)

    n_total = profile.n_total or profile.n_sampled
    if profile.kind == "points" and n_total:
        # batch at a pow2 that amortizes dispatch overhead but keeps the
        # probe intermediates bounded — sized from the FULL workload (the
        # profiling sample is capped; chunking a large stream at the
        # sample size would multiply dispatches ~50x)
        batch = min(65536, max(1024, _next_pow2(n_total // 8)))
        set_knob(
            "batch_size", batch, "pow2-amortized-chunks",
            {"n_total": n_total},
        )
        set_knob(
            "bucket_min", max(64, batch // 16), "ladder-spans-batch",
            {"batch_size": batch},
        )
        set_knob(
            "bucket_max", batch, "ladder-spans-batch",
            {"batch_size": batch},
        )

    if profile.band_fraction is not None and profile.band_fraction > 0.05:
        # a fat epsilon band means the f64 recheck dominates — the exact
        # fold lane keeps zonal answers bit-identical without a recheck
        set_knob(
            "zonal_lane", "fold", "band-fraction-exactness",
            {"band_fraction": profile.band_fraction},
        )

    if profile.kind == "raster" and profile.tile_occupancy is not None:
        if profile.tile_occupancy < SPARSE_TILE_OCCUPANCY:
            set_knob(
                "raster_tile", (128, 128), "sparse-raster-small-tiles",
                {"tile_occupancy": profile.tile_occupancy,
                 "threshold": SPARSE_TILE_OCCUPANCY},
            )
        else:
            set_knob(
                "raster_tile", (256, 256), "dense-raster-default-tiles",
                {"tile_occupancy": profile.tile_occupancy,
                 "threshold": SPARSE_TILE_OCCUPANCY},
            )

    out.source = {"profile": profile.as_dict()}
    _telemetry.record(
        "tune_recommend",
        kind=profile.kind,
        knobs=",".join(sorted(r["knob"] for r in why)),
        rules=",".join(sorted({r["rule"] for r in why})),
    )
    return out
