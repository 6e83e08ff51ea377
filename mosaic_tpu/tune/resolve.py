"""Knob-precedence resolution: explicit arg > env knob > profile > default.

Every frontend that accepts ``profile=`` (`pip_join`, `StreamJoin`,
`ServeEngine`, `ZonalEngine`, `RasterStream`) funnels its profile-consumed
knobs through :func:`resolve_knobs` at the HOST entry point, before any
value is closed over by a jitted program — the same staging discipline as
`join.resolve_probe_mode` / `zonal.resolve_zonal_lane`, and the mosaic-lint
``env-read-after-staging`` rule keeps it machine-checked. The precedence is
the single documented order (ARCHITECTURE "Workload optimizer"):

    explicit argument  >  env knob  >  TuningProfile  >  built-in default

Knobs that already had an env spelling keep it (``MOSAIC_STREAM_WINDOW``,
``MOSAIC_STREAM_PIPELINE``, ``MOSAIC_RASTER_TILE``, ``MOSAIC_RASTER_LANE``);
tune-only knobs read the ``MOSAIC_TUNE_*`` family (``MOSAIC_TUNE_PROBE``,
``MOSAIC_TUNE_WRITEBACK``, ``MOSAIC_TUNE_BATCH``, ``MOSAIC_TUNE_BUCKET_MIN``,
``MOSAIC_TUNE_BUCKET_MAX``, ``MOSAIC_TUNE_KNN_LANE``). ``resolution`` has
deliberately NO env layer: it changes the tessellation artifact, not just
the execution schedule, so it only flows explicitly or via a profile.

Each entry-point call records ONE ``tune_resolve`` telemetry event naming
every resolved knob's value and source — the precedence tests assert on
that event, so the order is machine-checkable per frontend.
"""

from __future__ import annotations

import os

from ..runtime import telemetry as _telemetry


def _parse_bool(raw: str):
    return raw not in ("", "0")


def _parse_tile(raw: str):
    th, tw = (int(p) for p in raw.lower().split("x"))
    if th < 1 or tw < 1:
        raise ValueError(raw)
    return th, tw


#: tune-only knobs: profile field -> (MOSAIC_TUNE_ env suffix, parser)
_TUNE_ENV = {
    "probe": ("PROBE", str),
    "writeback": ("WRITEBACK", str),
    "batch_size": ("BATCH", int),
    "bucket_min": ("BUCKET_MIN", int),
    "bucket_max": ("BUCKET_MAX", int),
    "knn_lane": ("KNN_LANE", str),
}

#: knobs whose env spelling predates the tune subsystem (kept verbatim so
#: existing deployments keep working): profile field -> (reader, parser).
#: The readers keep the names as LITERAL os.environ.get calls so the
#: project-registry env scan (and hence the docs drift rule) still sees
#: every spelling.
_SHARED_ENV = {
    "stream_window": (
        lambda: os.environ.get("MOSAIC_STREAM_WINDOW"), int,
    ),
    "stream_pipeline": (
        lambda: os.environ.get("MOSAIC_STREAM_PIPELINE"), _parse_bool,
    ),
    "raster_tile": (
        lambda: os.environ.get("MOSAIC_RASTER_TILE"), _parse_tile,
    ),
    "zonal_lane": (
        lambda: os.environ.get("MOSAIC_RASTER_LANE"), str,
    ),
}

#: knobs with no env layer at all (artifact-changing, not schedule-changing)
_NO_ENV = frozenset({"resolution"})

KNOBS = tuple(sorted({*_TUNE_ENV, *_SHARED_ENV, *_NO_ENV}))


def _env_value(name: str):
    """The env layer's parsed value for one knob, or None when unset.
    Reads happen here — host resolution code, never traced — which is
    what keeps the ``env-read-after-staging`` lint rule green."""
    if name in _TUNE_ENV:
        suffix, parse = _TUNE_ENV[name]
        raw = os.environ.get(f"MOSAIC_TUNE_{suffix}")
    elif name in _SHARED_ENV:
        read, parse = _SHARED_ENV[name]
        raw = read()
    else:
        return None
    if raw is None or raw == "":
        return None
    try:
        return parse(raw)
    except (TypeError, ValueError) as exc:
        raise ValueError(
            f"malformed env value for tune knob {name!r}: {raw!r}"
        ) from exc


def resolve_knob(name: str, explicit, profile, default):
    """One knob through the precedence chain; returns ``(value, source)``
    with source in ``explicit|env|profile|default``. ``explicit=None``
    means "caller did not pass it" — frontends use None sentinels for
    exactly this reason."""
    if name not in KNOBS:
        raise KeyError(f"unknown tune knob {name!r} (expected one of {KNOBS})")
    if explicit is not None:
        return explicit, "explicit"
    env = _env_value(name)
    if env is not None:
        return env, "env"
    pval = getattr(profile, name, None) if profile is not None else None
    if pval is not None:
        return pval, "profile"
    return default, "default"


class ResolvedKnobs(dict):
    """``{knob: value}``, with ``sources``: ``{knob: explicit|env|
    profile|default}`` — which layer of the precedence gave each value."""

    sources: dict


def resolve_knobs(
    entry: str, profile, *, explicit: dict, defaults: dict
) -> ResolvedKnobs:
    """Resolve every knob in ``explicit``/``defaults`` for one frontend
    entry point and record the single summarizing ``tune_resolve``
    telemetry event. Returns ``{knob: value}`` (a `ResolvedKnobs`)."""
    values, sources = ResolvedKnobs(), {}
    values.sources = sources
    for name, default in defaults.items():
        values[name], sources[name] = resolve_knob(
            name, explicit.get(name), profile, default
        )
    _telemetry.record(
        "tune_resolve",
        entry=entry,
        profiled=profile is not None,
        **{f"{k}_source": s for k, s in sources.items()},
        **{
            k: (v if isinstance(v, (int, float, bool, str, type(None))) else repr(v))
            for k, v in values.items()
        },
    )
    return values
