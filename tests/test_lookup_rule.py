"""The tier-1 row-fetch lane (`lookup`) is chosen in ONE place,
`sql.join.resolve_lookup`: choices only here (which lane, from which
source) — what each lane costs is a chip reading (PERF.md section 6,
PR 25).
"""

from types import SimpleNamespace

import numpy as np
import pytest

from mosaic_tpu.core.geometry import wkt
from mosaic_tpu.core.index import CustomIndexSystem, GridConf
from mosaic_tpu.core.tessellate import tessellate
from mosaic_tpu.dispatch.core import DispatchCore
from mosaic_tpu.runtime import telemetry
from mosaic_tpu.serve import BucketLadder, ServeEngine
from mosaic_tpu.sql import join as J
from mosaic_tpu.sql import stream as S
from mosaic_tpu.tune import TuningProfile

CUSTOM = CustomIndexSystem(GridConf(-180, 180, -90, 90, 2, 10.0, 10.0))
RES = 3
ZONES = [
    "POLYGON ((1 1, 13 2, 12 11, 6 14, 2 9, 1 1), "
    "(5 5, 5 8, 8 8, 8 5, 5 5))",
    "POLYGON ((20 0, 30 0, 30 10, 25 4, 20 10, 20 0))",
    "POLYGON ((-20 -20, -5 -20, -5 -5, -20 -5, -20 -20))",
]
BBOX = (-25.0, -25.0, 35.0, 20.0)


def _table(cells: int, dtype=np.float32):
    """All the resolver reads of an index: the tier-1 edge table."""
    return SimpleNamespace(cell_edges=np.zeros((cells, 1, 4), dtype))


@pytest.fixture(autouse=True)
def _clean_env(monkeypatch):
    monkeypatch.delenv("MOSAIC_TUNE_LOOKUP", raising=False)


@pytest.fixture(scope="module")
def index():
    return J.build_chip_index(
        tessellate(wkt.from_wkt(ZONES), CUSTOM, RES, keep_core_geoms=False)
    )


@pytest.fixture(scope="module")
def points():
    rng = np.random.default_rng(11)
    return rng.uniform(BBOX[:2], BBOX[2:], (2048, 2))


def _one_event(events):
    (ev,) = [e for e in events if e["event"] == "join_lookup"]
    return ev


@pytest.mark.parametrize(
    "cells,dtype",
    [
        (1287, np.float32),  # the 35-zone fixture of round 5
        (1427, np.float32),  # smallest index read on the chip in PR 25
        (33898, np.float32),  # the Quickstart deployment
        (1287, np.float64),
    ],
)
def test_auto_is_the_row_gather_for_every_index(cells, dtype):
    """The one-hot lost to the two-gather fetch at every index size read
    on the v5e (1,427 to 33,898 cells: PERF.md section 6, PR 25), so auto
    no longer asks the platform or the cell count."""
    with telemetry.capture() as events:
        got = J.resolve_lookup(None, _table(cells, dtype))
    assert got == "gather"
    ev = _one_event(events)
    assert (ev["lookup"], ev["cells"], ev["source"]) == (
        "gather", cells, "auto"
    )


@pytest.mark.parametrize("lane", ["gather", "mxu", "mxu2"])
def test_a_given_lane_passes_through(lane):
    with telemetry.capture() as events:
        assert J.resolve_lookup(lane, _table(33898)) == lane
    assert _one_event(events)["source"] == "explicit"


def test_unknown_lane_is_refused():
    with pytest.raises(ValueError, match="lookup must be one of"):
        J.resolve_lookup("onehot", _table(10))


@pytest.mark.parametrize(
    "explicit,env,profile,want,source",
    [
        (None, None, None, "gather", "auto"),
        (None, None, "mxu", "mxu", "profile"),
        (None, "mxu2", "mxu", "mxu2", "env"),
        ("mxu", "mxu2", "gather", "mxu", "explicit"),
    ],
)
def test_precedence_over_auto(
    index, monkeypatch, explicit, env, profile, want, source
):
    """Explicit argument > MOSAIC_TUNE_LOOKUP > TuningProfile > auto,
    as `tune/resolve.py` orders them; the event says which it was."""
    if env is not None:
        monkeypatch.setenv("MOSAIC_TUNE_LOOKUP", env)
    prof = None if profile is None else TuningProfile(lookup=profile)
    with telemetry.capture() as events:
        sj = S.StreamJoin(index, CUSTOM, RES, lookup=explicit, profile=prof)
    assert sj.lookup == want
    ev = _one_event(events)
    assert (ev["lookup"], ev["source"]) == (want, source)
    assert ev["cells"] == index.cell_edges.shape[0]


@pytest.mark.parametrize("entry", ["pip_join", "stream_join", "dispatch_core"])
def test_entry_points_take_the_resolvers_answer(
    index, points, monkeypatch, entry
):
    """Each entry point asks `resolve_lookup` about ITS index and runs the
    lane it names — here `mxu`, which auto never picks."""
    asked = []

    def resolver(lookup, idx, *, source="explicit"):
        asked.append((lookup, idx))
        return "mxu"

    monkeypatch.setattr(J, "resolve_lookup", resolver)
    monkeypatch.setattr(S, "resolve_lookup", resolver)
    want = J.pip_join(
        points, None, CUSTOM, RES, chip_index=index, lookup="gather"
    )
    asked.clear()
    if entry == "pip_join":
        seen = []
        real = J._dispatch.jit_join

        def spy():
            fn = real()

            def call(*a, **kw):
                seen.append(kw["lookup"])
                return fn(*a, **kw)

            return call

        monkeypatch.setattr(J._dispatch, "jit_join", spy)
        got = J.pip_join(points, None, CUSTOM, RES, chip_index=index)
        assert seen and set(seen) == {"mxu"}
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    elif entry == "stream_join":
        assert S.StreamJoin(index, CUSTOM, RES).lookup == "mxu"
    else:
        assert DispatchCore(index, CUSTOM, RES).lookup == "mxu"
    assert len(asked) == 1
    assert asked[0][0] is None and asked[0][1] is index


def test_serve_engine_reports_its_lane(index):
    """`engine.metrics()["lookup"]` names the lane the core resolved, and
    a `hot_swap` keeps a lane that was given."""
    ladder = BucketLadder(64, 64)
    with ServeEngine(index, CUSTOM, RES, ladder=ladder) as eng:
        assert eng.metrics()["lookup"] == eng.lookup == "gather"
    with ServeEngine(index, CUSTOM, RES, ladder=ladder, lookup="mxu") as eng:
        eng.hot_swap(index)
        assert eng.metrics()["lookup"] == eng.lookup == "mxu"
