"""Runtime resilience layer: capacity escalation, transient retry,
graceful degradation, fault injection (`mosaic_tpu/runtime/`).

The acceptance contract (ISSUE 1): under injected faults — forced
tier-2 overflow with shrunken caps, synthetic transient device errors on
the first N calls — `pip_join`, `overlay_join`, and `dist_pip_join`
return results bit-identical to the clean run with the escalation/retry
trail visible in structured telemetry; a fault that exhausts the bounded
budget raises a typed error or returns an explicitly ``degraded``
host-oracle result. Never a silent ``-2``/zeroed output.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from mosaic_tpu.core.index.h3 import H3IndexSystem
from mosaic_tpu.core.tessellate import tessellate
from mosaic_tpu.datasets import random_points, synthetic_zones
from mosaic_tpu.parallel import dist_pip_join, make_mesh
from mosaic_tpu.runtime import (
    CapacityOverflow,
    DegradedResult,
    EscalationPolicy,
    RetryExhausted,
    RetryPolicy,
    TransientDeviceError,
    backoff_delays,
    call_with_retry,
    faults,
    is_transient,
    run_escalating,
    telemetry,
)
from mosaic_tpu.sql.join import OVERFLOW, build_chip_index, pip_join
from mosaic_tpu.sql.overlay import overlay_join
from mosaic_tpu.sql import pip_join_points

RES = 7
BBOX = (-74.05, 40.60, -73.85, 40.78)
N_POINTS = 1200


@pytest.fixture(scope="module")
def problem():
    """Zones + a chip index built with a tiny edge_cap so heavy (tier-2)
    cells genuinely exist, points, and the clean join result."""
    h3 = H3IndexSystem()
    zones = synthetic_zones(3, 3, bbox=BBOX)
    table = tessellate(zones, h3, RES, keep_core_geoms=False)
    index = build_chip_index(table, edge_cap=8)
    assert index.num_heavy_cells > 0  # tier 2 must be exercised
    pts = random_points(N_POINTS, bbox=BBOX, seed=5)
    clean = np.asarray(
        pip_join(pts, None, h3, RES, chip_index=index, recheck=False)
    )
    assert (clean >= 0).any() and (clean != OVERFLOW).all()
    return h3, zones, index, pts, clean


# ------------------------------------------------------------ primitives


def test_backoff_delays_grow_and_cap():
    pol = RetryPolicy(base_delay_s=1.0, max_delay_s=4.0, jitter=0.0)
    d = backoff_delays(pol)
    assert [next(d) for _ in range(4)] == [1.0, 2.0, 4.0, 4.0]


def test_is_transient_classification():
    assert is_transient(TransientDeviceError("x"))
    assert is_transient(RuntimeError("UNAVAILABLE: connection reset by peer"))
    # deterministic compile refusals must raise at once, never retry
    # into the host oracle (XLA and Mosaic report them under these codes)
    assert not is_transient(
        RuntimeError("INTERNAL: Mosaic failed to compile TPU kernel")
    )
    assert not is_transient(
        RuntimeError("RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem")
    )
    assert not is_transient(ValueError("bad argument"))
    assert not is_transient(RuntimeError("shape mismatch"))
    assert not is_transient(TypeError("nope"))


def test_call_with_retry_recovers_and_telemetry():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise TransientDeviceError("boom")
        return 42

    with telemetry.capture() as ev:
        out = call_with_retry(
            flaky, policy=RetryPolicy(base_delay_s=0.0), label="t"
        )
    assert out == 42 and calls["n"] == 3
    assert [e["event"] for e in ev] == ["transient_retry", "transient_retry"]
    assert ev[0]["attempt"] == 1 and ev[1]["attempt"] == 2


def test_call_with_retry_nontransient_raises_immediately():
    calls = {"n": 0}

    def bad():
        calls["n"] += 1
        raise ValueError("programming error")

    with pytest.raises(ValueError):
        call_with_retry(bad, policy=RetryPolicy(base_delay_s=0.0))
    assert calls["n"] == 1


def test_call_with_retry_exhausts_typed():
    def always():
        raise TransientDeviceError("down")

    with pytest.raises(RetryExhausted) as ei:
        call_with_retry(
            always, policy=RetryPolicy(max_attempts=2, base_delay_s=0.0)
        )
    assert ei.value.attempts == 2
    assert isinstance(ei.value.last, TransientDeviceError)


def test_call_with_retry_fallback_is_degraded():
    def always():
        raise TransientDeviceError("down")

    out = call_with_retry(
        always,
        policy=RetryPolicy(max_attempts=2, base_delay_s=0.0),
        fallback=lambda: np.arange(4),
    )
    assert isinstance(out, DegradedResult) and out.degraded
    np.testing.assert_array_equal(np.asarray(out), np.arange(4))
    # a DegradedResult behaves like its base array everywhere else
    assert int(out.sum()) == 6


def test_run_escalating_grows_to_exact():
    seen = []

    def attempt(caps):
        seen.append(dict(caps))
        return caps["cap"]

    with telemetry.capture() as ev:
        out, caps = run_escalating(
            attempt, {"cap": 8}, {"cap": 1024},
            overflow_count=lambda c: 0 if c >= 32 else 32 - c,
            stage="unit",
        )
    assert out == 32 and caps["cap"] == 32
    assert [c["cap"] for c in seen] == [8, 16, 32]
    kinds = [e["event"] for e in ev]
    assert kinds.count("capacity_overflow") == 2
    assert kinds[-1] == "escalation_resolved"


def test_run_escalating_ceiling_raises_typed():
    with pytest.raises(CapacityOverflow) as ei:
        run_escalating(
            lambda caps: caps["cap"], {"cap": 8}, {"cap": 16},
            overflow_count=lambda c: 1, stage="unit",
        )
    assert ei.value.stage == "unit" and ei.value.overflow_count == 1


def test_run_escalating_attempt_budget_raises_typed():
    with pytest.raises(CapacityOverflow):
        run_escalating(
            lambda caps: caps["cap"], {"cap": 8}, {"cap": 1 << 40},
            overflow_count=lambda c: 1,
            policy=EscalationPolicy(max_attempts=3),
        )


def test_faults_site_filtering():
    with faults.transient_errors(5, sites=("other.site",)):
        faults.maybe_fail("this.site")  # no match: must not raise
    with faults.transient_errors(1, sites=("knn.*",)):
        with pytest.raises(TransientDeviceError):
            faults.maybe_fail("knn.pair_distances")
        faults.maybe_fail("knn.pair_distances")  # budget of 1 spent


def test_faults_clamp_caps_noop_without_plan():
    caps = {"found_cap": 512, "heavy_cap": None}
    assert faults.clamp_caps(caps) == caps
    with faults.shrink_caps(found_cap=8, heavy_cap=8):
        out = faults.clamp_caps(caps)
    assert out == {"found_cap": 8, "heavy_cap": 8}


# ------------------------------------------------- pip_join under faults


def test_pip_join_forced_overflow_escalates_bit_identical(problem):
    h3, zones, index, pts, clean = problem
    with telemetry.capture() as ev:
        with faults.shrink_caps(found_cap=128, heavy_cap=32):
            out = pip_join(
                pts, None, h3, RES, chip_index=index, recheck=False
            )
    out = np.asarray(out)
    np.testing.assert_array_equal(out, clean)
    assert (out != OVERFLOW).all()
    # ignore span events (obs tracing closes the join.pip span after the
    # escalation trail) — the resilience trail itself ends resolved
    kinds = [e["event"] for e in ev if e["event"] != "span"]
    assert "capacity_overflow" in kinds  # the trail is visible
    assert kinds[-1] == "escalation_resolved"


def test_pip_join_forced_tier2_overflow_bit_identical(problem):
    h3, zones, index, pts, clean = problem
    with telemetry.capture() as ev:
        with faults.force_tier2_overflow(heavy_cap=8):
            out = pip_join(
                pts, None, h3, RES, chip_index=index, recheck=False
            )
    np.testing.assert_array_equal(np.asarray(out), clean)
    over = [e for e in ev if e["event"] == "capacity_overflow"]
    assert over and all(e["caps"]["heavy_cap"] >= 8 for e in over)


def test_pip_join_transient_faults_retry_bit_identical(problem):
    h3, zones, index, pts, clean = problem
    with telemetry.capture() as ev:
        with faults.transient_errors(2, sites=("pip_join.device",)):
            out = pip_join(
                pts, None, h3, RES, chip_index=index, recheck=False
            )
    assert not isinstance(out, DegradedResult)  # retries recovered
    np.testing.assert_array_equal(np.asarray(out), clean)
    assert [e["event"] for e in ev].count("transient_retry") == 2


def test_pip_join_retry_exhausted_degrades_to_host_oracle(problem):
    h3, zones, index, pts, clean = problem
    from mosaic_tpu.sql.join import host_join

    with telemetry.capture() as ev:
        with faults.transient_errors(50, sites=("pip_join.device",)):
            out = pip_join(
                pts, None, h3, RES, chip_index=index, recheck=False
            )
    assert isinstance(out, DegradedResult) and out.degraded
    assert out.attempts >= 3 and "exhausted" in out.reason
    # the degraded answer is the exact f64 host oracle, not zeros
    expect = host_join(pts, index.host, h3, RES)
    np.testing.assert_array_equal(np.asarray(out), expect)
    assert any(e["event"] == "degraded" for e in ev)


def test_pip_join_compile_failure_raises_not_degrades(problem):
    """A deterministic compile refusal (XLA and Mosaic report them as
    ``INTERNAL``) must surface at once: retried and then answered by the
    host oracle, the process would exit 0 having run nothing on the
    device."""
    h3, zones, index, pts, clean = problem

    def refusal(site):
        return RuntimeError(
            "INTERNAL: Mosaic failed to compile TPU kernel: "
            "scoped vmem limit exceeded"
        )

    with telemetry.capture() as ev:
        with faults.transient_errors(
            50, sites=("pip_join.device",), exc_factory=refusal
        ) as plan:
            with pytest.raises(RuntimeError, match="Mosaic failed to compile"):
                pip_join(pts, None, h3, RES, chip_index=index, recheck=False)
    assert plan.failed == 1  # raised on the first attempt, no retry
    kinds = {e["event"] for e in ev}
    assert not kinds & {"transient_retry", "retry_exhausted", "degraded"}


def test_pip_join_points_still_reports_overflow_at_low_level(problem):
    """The LOW-level jittable API keeps the documented -2 contract; only
    the managed wrappers escalate. This pins that the sentinel survives
    for callers that size caps themselves."""
    h3, zones, index, pts, clean = problem
    shift = index.host.shift
    dt = np.asarray(index.border.verts).dtype
    cells = np.asarray(h3.point_to_cell(jnp.asarray(pts), RES))
    out = np.asarray(
        pip_join_points(
            jnp.asarray((pts - shift).astype(dt)), jnp.asarray(cells),
            index, found_cap=8,
        )
    )
    assert (out == OVERFLOW).any()


# --------------------------------------------- overlay_join under faults


@pytest.fixture(scope="module")
def overlay_problem():
    h3 = H3IndexSystem()
    left = synthetic_zones(3, 3, bbox=BBOX)
    right = synthetic_zones(2, 2, bbox=BBOX)
    clean = np.asarray(overlay_join(left, right, h3, RES))
    assert clean.shape[0] > 0
    return h3, left, right, clean


def test_overlay_transient_retry_bit_identical(overlay_problem):
    h3, left, right, clean = overlay_problem
    with telemetry.capture() as ev:
        with faults.transient_errors(2, sites=("overlay.predicate",)):
            out = overlay_join(left, right, h3, RES)
    assert not isinstance(out, DegradedResult)
    np.testing.assert_array_equal(np.asarray(out), clean)
    assert [e["event"] for e in ev].count("transient_retry") == 2


def test_overlay_oracle_exhaustion_raises_typed(overlay_problem):
    h3, left, right, clean = overlay_problem
    with faults.transient_errors(99, sites=("overlay.predicate",)):
        with pytest.raises(RetryExhausted):
            overlay_join(left, right, h3, RES)


def test_overlay_device_backend_degrades_to_oracle(overlay_problem):
    h3, left, right, clean = overlay_problem
    with faults.transient_errors(99, sites=("overlay.predicate",)):
        out = overlay_join(left, right, h3, RES, backend="device")
    assert isinstance(out, DegradedResult) and out.degraded
    np.testing.assert_array_equal(np.asarray(out), clean)


# -------------------------------------------- dist_pip_join under faults


def test_dist_pip_join_clean_matches_pip_join(problem, devices):
    h3, zones, index, pts, clean = problem
    mesh = make_mesh(8, cell_axis=2)
    cells = np.asarray(h3.point_to_cell(jnp.asarray(pts), RES))
    match, counts = dist_pip_join(pts, cells, index, mesh, len(zones))
    np.testing.assert_array_equal(match, clean)
    expect = np.bincount(clean[clean >= 0], minlength=len(zones))
    np.testing.assert_array_equal(counts, expect)


def test_dist_pip_join_faults_bit_identical(problem, devices):
    """The headline acceptance: shrunken caps AND two transient failures
    — the distributed join still converges to the clean answer."""
    h3, zones, index, pts, clean = problem
    mesh = make_mesh(8, cell_axis=2)
    cells = np.asarray(h3.point_to_cell(jnp.asarray(pts), RES))
    with telemetry.capture() as ev:
        with faults.shrink_caps(found_cap=16, heavy_cap=16):
            with faults.transient_errors(2, sites=("dist_join.step",)):
                match, counts = dist_pip_join(
                    pts, cells, index, mesh, len(zones)
                )
    np.testing.assert_array_equal(match, clean)
    assert (match != OVERFLOW).all()
    kinds = [e["event"] for e in ev]
    assert kinds.count("transient_retry") == 2
    assert "capacity_overflow" in kinds and "escalation_resolved" in kinds


def test_dist_pip_join_exhaustion_degrades(problem, devices):
    h3, zones, index, pts, clean = problem
    mesh = make_mesh(8, cell_axis=2)
    cells = np.asarray(h3.point_to_cell(jnp.asarray(pts), RES))
    with faults.transient_errors(99, sites=("dist_join.step",)):
        match, counts = dist_pip_join(pts, cells, index, mesh, len(zones))
    assert isinstance(match, DegradedResult) and match.degraded
    from mosaic_tpu.sql.join import host_join_with_cells

    expect = host_join_with_cells(pts, cells, index.host)
    np.testing.assert_array_equal(np.asarray(match), expect)
    np.testing.assert_array_equal(
        counts, np.bincount(expect[expect >= 0], minlength=len(zones))
    )


# ------------------------------------------------------ KNN under faults


def test_knn_degrades_to_oracle_distances(problem):
    from mosaic_tpu.models import SpatialKNN

    h3, zones, index, pts, clean = problem
    lands = synthetic_zones(2, 2, bbox=(-74.0, 40.62, -73.9, 40.7))
    knn = SpatialKNN(index=h3, resolution=RES, k_neighbours=2)
    ref = knn.transform(lands, zones)
    assert ref.metrics["degraded"] is False
    knn2 = SpatialKNN(index=h3, resolution=RES, k_neighbours=2)
    with faults.transient_errors(999, sites=("knn.pair_distances",)):
        out = knn2.transform(lands, zones)
    assert out.metrics["degraded"] is True
    np.testing.assert_array_equal(out.candidate_id, ref.candidate_id)
    np.testing.assert_allclose(out.distance, ref.distance, rtol=1e-9)
