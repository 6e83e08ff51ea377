"""A durable job as a deployment runs it (PR 52): ``run_durable`` → a fatal
loss → a FRESH `StreamJoin` on the same index → ``resume``, for both segment
loops and every kill point, held to an unbroken ``run`` bit for bit and to
the benchmark's plain reference (`benchmark/references/pip_bruteforce.py`:
ray casting on the zone rings, nothing of the program). And the spans and
counters the cell `taxi.stream-durable` reads: one ``stream.fingerprint`` a
``run_durable`` and one a ``resume`` (two before PR 52), ``stream.resume``
with its ``ready_s`` and its ``stream.resume.load`` child, ``nbytes`` and
``write_s`` on every snapshot span, ``replayed_batches`` on a resumed run's
root. All on the CPU at a small size: answers and counts, never a time."""

import os

import numpy as np
import pytest

from benchmark.generators import zones
from benchmark.references import pip_bruteforce
from mosaic_tpu.core.index import CustomIndexSystem, GridConf
from mosaic_tpu.core.tessellate import tessellate
from mosaic_tpu.core.types import GeometryBuilder, GeometryType
from mosaic_tpu.runtime import RetryPolicy, checkpoint, faults, telemetry
from mosaic_tpu.sql.join import build_chip_index
from mosaic_tpu.sql.stream import StreamJoin, ring_from_host

CUSTOM = CustomIndexSystem(GridConf(-180, 180, -90, 90, 2, 10.0, 10.0))
RES = 3
BOX = (-25.0, -25.0, 35.0, 20.0)
K, BATCH, NB, SNAP = 3, 1024, 8, 2  # boundaries at 2, 4, 6, 8
FAST = RetryPolicy(max_attempts=3, base_delay_s=0.0, jitter=0.0)


@pytest.fixture(scope="module")
def rings():
    return zones.star_lattice(3, 3, BOX, seed=7, verts=10, jitter=0.45)


@pytest.fixture(scope="module")
def index(rings):
    b = GeometryBuilder()
    for ring in rings:
        b.add_geometry(GeometryType.POLYGON, [[ring]], srid=4326)
    return build_chip_index(
        tessellate(b.build(), CUSTOM, RES, keep_core_geoms=False)
    )


@pytest.fixture(scope="module")
def ring():
    rng = np.random.default_rng(11)
    return ring_from_host(
        [rng.uniform(BOX[:2], BOX[2:], (BATCH, 2)) for _ in range(K)]
    )


@pytest.fixture(scope="module")
def whole(index, ring):
    return StreamJoin(index, CUSTOM, RES).run(ring, NB, collect=True)


def _fold(r):
    return (r.checksum, r.matches, r.overflow)


def _job(index, ring, run_dir, kill_segment, *, pipeline, collect=True):
    """The killed run's worker is dropped; a fresh one resumes."""
    with faults.inject(
        fail_first=99, skip_first=kill_segment, sites=("stream.scan_step",),
        exc_factory=lambda site: RuntimeError(f"device lost @ {site}"),
    ):
        with pytest.raises(RuntimeError, match="device lost"):
            StreamJoin(index, CUSTOM, RES).run_durable(
                ring, NB, run_dir=run_dir, snapshot_every=SNAP,
                collect=collect, retry_policy=FAST, pipeline=pipeline,
            )
    return StreamJoin(index, CUSTOM, RES).resume(
        run_dir, ring, collect=collect, retry_policy=FAST, pipeline=pipeline,
    )


@pytest.mark.parametrize("pipeline", [False, True], ids=["sync", "pipelined"])
@pytest.mark.parametrize("kill_segment", [1, 2, 3])
def test_killed_and_resumed_job_is_the_unbroken_run_and_the_plain_reference(
        index, ring, rings, whole, tmp_path, pipeline, kill_segment):
    r = _job(index, ring, str(tmp_path), kill_segment, pipeline=pipeline)
    # exactly once: the fold of the whole job, whatever was replayed
    assert _fold(r) == _fold(whole)
    # bounded loss: a snapshot boundary, no later than the kill, and at
    # most snapshot_every x (1 + segments in flight) batches before it
    start = r.metrics["resumed_from"]
    kill_step = kill_segment * SNAP
    in_flight = 4 if pipeline else 0
    assert start % SNAP == 0 and start <= kill_step
    assert kill_step - start <= SNAP * (1 + in_flight)
    assert bool(r.metrics.get("pipeline")) is pipeline
    assert r.metrics["degraded"] is False
    # the rows this call ran are the unbroken run's rows for those batches
    assert r.outs.shape == (NB - start, BATCH)
    assert np.array_equal(r.outs, whole.outs[start:])
    # ... and the plain reference's, under the stream's stated limit
    pts = np.concatenate(
        [np.asarray(ring[i % K]) for i in range(start, NB)])
    want = pip_bruteforce.answers(rings, pts)
    assert (r.outs.reshape(-1) != want).mean() <= 0.001
    assert 0.05 < (want >= 0).mean() < 0.95  # hits and misses both
    assert checkpoint.list_snapshots(str(tmp_path)) == [2, 4, 6, 8]


def _spans(events, name):
    return [e for e in events if e.get("event") == "span"
            and e.get("name") == name]


@pytest.mark.parametrize("pipeline", [False, True], ids=["sync", "pipelined"])
def test_the_ring_is_hashed_once_a_run_and_once_a_resume(
        index, ring, whole, tmp_path, pipeline):
    d = str(tmp_path)
    with telemetry.capture() as killed:
        with faults.inject(
            fail_first=99, skip_first=2, sites=("stream.scan_step",),
            exc_factory=lambda site: RuntimeError("device lost"),
        ):
            with pytest.raises(RuntimeError):
                StreamJoin(index, CUSTOM, RES).run_durable(
                    ring, NB, run_dir=d, snapshot_every=SNAP,
                    retry_policy=FAST, pipeline=pipeline,
                )
    assert len(_spans(killed, "stream.fingerprint")) == 1
    assert not _spans(killed, "stream.resume")
    with telemetry.capture() as resumed:
        r = StreamJoin(index, CUSTOM, RES).resume(
            d, ring, retry_policy=FAST, pipeline=pipeline)
    assert _fold(r) == _fold(whole)
    hashes = _spans(resumed, "stream.fingerprint")
    assert len(hashes) == 1  # two before PR 52: `resume`, then the run
    assert hashes[0]["nbytes"] == K * BATCH * 2 * 8
    # no validation went with the second hash: another ring is refused
    other = ring_from_host(list(np.asarray(ring) + 1.0))
    with pytest.raises(ValueError, match="fingerprint"):
        StreamJoin(index, CUSTOM, RES).resume(d, other, retry_policy=FAST)


def test_resume_span_its_children_and_the_snapshot_attributes(
        index, ring, tmp_path):
    d = str(tmp_path)
    with faults.inject(
        fail_first=99, skip_first=2, sites=("stream.scan_step",),
        exc_factory=lambda site: RuntimeError("device lost"),
    ):
        with pytest.raises(RuntimeError):
            StreamJoin(index, CUSTOM, RES).run_durable(
                ring, NB, run_dir=d, snapshot_every=SNAP, retry_policy=FAST)
    with telemetry.capture() as ev:
        StreamJoin(index, CUSTOM, RES).resume(d, ring, retry_policy=FAST)
    (entry,) = _spans(ev, "stream.resume")
    (load,) = _spans(ev, "stream.resume.load")
    (fp,) = _spans(ev, "stream.fingerprint")
    assert load["parent_id"] == fp["parent_id"] == entry["span_id"]
    # up to the first resumed segment's launch: inside the whole, and the
    # load, the hash and the fresh worker's warm segment inside it
    (warm,) = [e for e in _spans(ev, "dispatch.compile")
               if e.get("site") == "stream.seg_loop"]
    assert load["seconds"] + fp["seconds"] + warm["seconds"] \
        <= entry["ready_s"] <= entry["seconds"]
    first = min(_spans(ev, "stream.segment"), key=lambda e: e["start_mono"])
    assert entry["start_mono"] + entry["ready_s"] <= first["start_mono"] + 1e-3
    (root,) = _spans(ev, "stream.durable_run")
    assert root["resumed_from"] == 4 and root["replayed_batches"] == 0
    snaps = _spans(ev, "stream.snapshot")
    assert [s["step"] for s in snaps] == [6, 8]
    for s in snaps:
        # the carry: a (3,) int32 fold and BATCH int64 prefetched cells
        assert s["nbytes"] == 3 * 4 + BATCH * 8
        assert 0.0 < s["write_s"] <= s["seconds"]


def test_replayed_batches_counts_what_a_skipped_snapshot_folds_again(
        index, ring, whole, tmp_path):
    d = str(tmp_path)
    with faults.inject(
        fail_first=99, skip_first=3, sites=("stream.scan_step",),
        exc_factory=lambda site: RuntimeError("device lost"),
    ):
        with pytest.raises(RuntimeError):
            StreamJoin(index, CUSTOM, RES).run_durable(
                ring, NB, run_dir=d, snapshot_every=SNAP, retry_policy=FAST)
    with open(os.path.join(d, "snap-00000006.npz"), "r+b") as f:
        f.truncate(64)  # a kill mid-write of the newest snapshot
    with telemetry.capture() as ev:
        r = StreamJoin(index, CUSTOM, RES).resume(d, ring, retry_policy=FAST)
    assert _fold(r) == _fold(whole) and r.metrics["resumed_from"] == 4
    (root,) = _spans(ev, "stream.durable_run")
    assert root["replayed_batches"] == SNAP  # batches 4 and 5, twice
    # a run that was not resumed says nothing of replays
    with telemetry.capture() as ev:
        StreamJoin(index, CUSTOM, RES).run_durable(
            ring, NB, run_dir=str(tmp_path / "fresh"), snapshot_every=SNAP)
    (root,) = _spans(ev, "stream.durable_run")
    assert "replayed_batches" not in root and not _spans(ev, "stream.resume")


def test_a_kill_and_a_fresh_workers_resume_read_as_one_trace(
        index, ring, tmp_path):
    """`stream.resume` joins the interrupted run's trace through the
    context every snapshot sidecar carries, BEFORE it loads anything, so
    its load and its ring hash are in that trace too: no orphan, no
    second root."""
    from mosaic_tpu import obs

    with telemetry.capture() as ev:
        _job(index, ring, str(tmp_path), 2, pipeline=False, collect=False)
    summ = obs.trace_summary(ev)
    assert len(summ) == 1, {k: v["names"] for k, v in summ.items()}
    (t,) = summ.values()
    assert t["roots"] == 1 and not t["orphans"], t
    for name in ("stream.resume", "stream.resume.load"):
        assert t["names"].count(name) == 1
    assert t["names"].count("stream.fingerprint") == 2  # the run's, the resume's
    assert t["names"].count("stream.durable_run") == 2
    killed, resumed = sorted(
        _spans(ev, "stream.durable_run"), key=lambda e: e["start_mono"])
    (entry,) = _spans(ev, "stream.resume")
    assert entry["parent_id"] == resumed["parent_id"] == killed["span_id"]


def test_the_segment_program_registers_its_stage_table(index, ring, tmp_path):
    """A traced durable run's device ops read by the join's own stage names:
    the warm-up tells `obs.stages` how to lower the segment program again
    (shapes only), as `run` does for the loop program."""
    from mosaic_tpu.obs import stages

    stages.clear()
    before = stages.lowerings()
    StreamJoin(index, CUSTOM, RES).run_durable(
        ring, NB, run_dir=str(tmp_path), snapshot_every=SNAP)
    assert ("jit_seg", BATCH) in stages.registered()
    assert stages.lowerings() == before  # the run itself lowers nothing
    table = stages.tables({"jit_seg"}, {BATCH})["jit_seg"]
    assert {"pip.cells", "pip.hash_probe", "pip.tier1", "stream.fold"} <= \
        set(table.values())
