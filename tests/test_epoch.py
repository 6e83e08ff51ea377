"""Epochal mutable-index contract (ISSUE 18): delta tessellation
bit-identity, atomic epoch publish, crash-consistent delta log with
kill-at-every-boundary replay, typed corruption refusals, compaction
(auto, background, and killed mid-way), the torn-publish boundary, the
durable-stream epoch fence, and the router's per-tenant epoch advance —
`mosaic_tpu/index/epoch.py` + the `core/tessellate.py` surgery."""

import numpy as np
import pytest

from mosaic_tpu.core.geometry import wkt
from mosaic_tpu.core.index import CustomIndexSystem, GridConf
from mosaic_tpu.core.tessellate import tessellate, tessellate_subset
from mosaic_tpu.index import (
    EpochalIndex,
    EpochFingerprintMismatch,
    EpochLogCorrupt,
    chip_index_equal,
)
from mosaic_tpu.raster import Raster
from mosaic_tpu.raster.zonal import host_zonal_zones_oracle, zonal_zones
from mosaic_tpu.runtime import checkpoint, faults, telemetry
from mosaic_tpu.runtime.errors import TransientDeviceError
from mosaic_tpu.runtime.retry import RetryPolicy
from mosaic_tpu.serve import BucketLadder, ServeEngine, ServeRouter
from mosaic_tpu.sql.join import build_chip_index, host_join, pip_join
from mosaic_tpu.sql.stream import StreamJoin, ring_from_host

CUSTOM = CustomIndexSystem(GridConf(-180, 180, -90, 90, 2, 10.0, 10.0))
RES = 3
BBOX = (-25.0, -25.0, 35.0, 20.0)
FAST = RetryPolicy(max_attempts=3, base_delay_s=0.0, jitter=0.0)

ZONES = [
    "POLYGON ((1 1, 13 2, 12 11, 6 14, 2 9, 1 1))",
    "POLYGON ((-20 -20, -5 -20, -5 -5, -20 -5, -20 -20))",
    "POLYGON ((20 -10, 30 -10, 30 5, 20 5, 20 -10))",
]
#: epoch 1: zone 1 grows (a live edit of an existing geometry)
ZONE1_V2 = "POLYGON ((-22 -22, -4 -22, -4 -4, -22 -4, -22 -22))"
#: epoch 2: a brand-new zone under a fresh stable id
ZONE3_NEW = "POLYGON ((-15 5, -5 5, -5 15, -15 15, -15 5))"


def mk(log_dir=None, **kw):
    kw.setdefault("keep_core_geoms", False)
    return EpochalIndex(
        wkt.from_wkt(ZONES), CUSTOM, RES,
        log_dir=str(log_dir) if log_dir else None, **kw,
    )


def scratch(ep):
    """The from-scratch oracle: a full tessellate + build of the
    epochal index's CURRENT column — what every published epoch must be
    bit-identical to."""
    return build_chip_index(
        tessellate(ep.column(), CUSTOM, RES, keep_core_geoms=False)
    )


def edit_replace(ep):
    return ep.apply(upsert=wkt.from_wkt([ZONE1_V2]), ids=[1])


def edit_insert(ep):
    return ep.apply(upsert=wkt.from_wkt([ZONE3_NEW]), ids=[3])


def edit_remove(ep):
    return ep.apply(remove=[0])


EDITS = (edit_replace, edit_insert, edit_remove)

BOOM = lambda s: RuntimeError(f"synthetic kill @ {s}")  # noqa: E731


@pytest.fixture(scope="module")
def pts():
    rng = np.random.default_rng(3)
    return rng.uniform(BBOX[:2], BBOX[2:], (256, 2))


@pytest.fixture(scope="module")
def advanced():
    """One epochal index driven through every edit kind and published
    at the final epoch (shared by the read-only frontend tests)."""
    ep = mk()
    for e in EDITS:
        e(ep)
    ep.publish()
    return ep


# ------------------------------------------------- delta tessellation


class TestDeltaTessellation:
    def test_subset_equals_full_blocks(self):
        """THE pin `tessellate_subset`'s docstring names: tessellation
        is per-geometry independent, so a subset pass is bit-identical
        to the matching blocks of a full pass."""
        col = wkt.from_wkt(ZONES)
        full = tessellate(col, CUSTOM, RES, keep_core_geoms=False)
        for g in range(len(ZONES)):
            sub = tessellate_subset(
                col, np.array([g]), CUSTOM, RES, keep_core_geoms=False
            )
            rows = np.nonzero(np.asarray(full.geom_id) == g)[0]
            assert len(sub) == rows.size
            np.testing.assert_array_equal(sub.geom_id, g)
            np.testing.assert_array_equal(
                sub.cell_id, np.asarray(full.cell_id)[rows]
            )
            np.testing.assert_array_equal(
                sub.is_core, np.asarray(full.is_core)[rows]
            )
            np.testing.assert_array_equal(
                sub.has_geom, np.asarray(full.has_geom)[rows]
            )
            want = full.chips.take([int(r) for r in rows])
            got = sub.chips
            for f in ("xy", "ring_offsets", "part_offsets",
                      "geom_offsets", "geom_type", "srid"):
                np.testing.assert_array_equal(
                    getattr(got, f), getattr(want, f)
                )

    def test_subset_relabels_geom_ids(self):
        col = wkt.from_wkt(ZONES)
        sub = tessellate_subset(
            col, np.array([0, 2]), CUSTOM, RES, keep_core_geoms=False,
            geom_ids=np.array([7, 9]),
        )
        assert set(np.unique(sub.geom_id)) == {7, 9}


# ------------------------------------------------- epoch bit-identity


class TestEpochBitIdentity:
    def test_epoch0_matches_scratch(self):
        ep = mk()
        ep.publish()
        assert ep.epoch == 0 and ep.applied_epoch == 0
        assert chip_index_equal(ep.index, scratch(ep))

    def test_every_epoch_matches_scratch(self):
        """The invariant everything else rides on: after replace,
        insert, and remove edits, each published epoch is bit-identical
        to a from-scratch rebuild of the current column."""
        ep = mk()
        ep.publish()
        for n, edit in enumerate(EDITS, start=1):
            stats = edit(ep)
            assert stats["epoch"] == n
            assert ep.applied_epoch == n and ep.epoch == n - 1
            ep.publish()
            assert ep.epoch == n
            assert chip_index_equal(ep.index, scratch(ep))
            assert ep.index.epoch == n
            assert ep.index.epoch_token == ep.epoch_token(n)

    def test_grow_from_empty(self):
        ep = EpochalIndex(None, CUSTOM, RES, keep_core_geoms=False)
        assert len(ep) == 0
        ep.apply(upsert=wkt.from_wkt(ZONES), ids=[0, 1, 2])
        ep.publish()
        assert chip_index_equal(ep.index, scratch(ep))

    def test_apply_validation(self):
        ep = mk()
        with pytest.raises(ValueError, match="ids for"):
            ep.apply(upsert=wkt.from_wkt([ZONE1_V2]), ids=[1, 2])
        with pytest.raises(ValueError, match="both upserted and removed"):
            ep.apply(upsert=wkt.from_wkt([ZONE1_V2]), ids=[1], remove=[1])
        with pytest.raises(KeyError, match="unknown geometry ids"):
            ep.apply(remove=[99])
        assert ep.applied_epoch == 0  # nothing durable happened

    def test_index_identity_carries_epoch_token(self, advanced):
        ident = checkpoint.index_identity(advanced.index)
        assert "@" in ident
        assert ident.endswith(advanced.index.epoch_token)
        plain = build_chip_index(
            tessellate(wkt.from_wkt(ZONES), CUSTOM, RES,
                       keep_core_geoms=False)
        )
        assert "@" not in checkpoint.index_identity(plain)


# ------------------------------------------------- frontends vs oracle


class TestFrontendsVsOracle:
    def test_pip_join_matches_f64_oracle(self, advanced, pts):
        got = pip_join(
            pts, None, CUSTOM, RES, chip_index=advanced.index,
            recheck=False,
        )
        want = host_join(pts, advanced.index.host, CUSTOM, RES)
        np.testing.assert_array_equal(np.asarray(got), want)

    def test_zonal_matches_f64_oracle(self, advanced):
        rng = np.random.default_rng(5)
        data = rng.uniform(0, 100, (1, 40, 40))
        data[0][rng.random((40, 40)) < 0.1] = -9.0
        r = Raster(
            data=data, gt=(-0.5, 1.0, 0.0, 15.5, 0.0, -1.0), srid=0,
            nodata=-9.0,
        )
        got = zonal_zones(r, advanced.index, CUSTOM, RES, tile=(32, 32))
        want = host_zonal_zones_oracle(
            r, advanced.index, CUSTOM, RES, tile=(32, 32)
        )
        np.testing.assert_array_equal(got.keys, want.keys)
        np.testing.assert_array_equal(got.count, want.count)
        np.testing.assert_array_equal(got.sum, want.sum)
        np.testing.assert_array_equal(got.min, want.min)
        np.testing.assert_array_equal(got.max, want.max)

    def test_serve_engine_spans_epochs(self, pts):
        """Live edits published INTO a running engine: every epoch's
        answers match that epoch's f64 oracle, and a publish that fails
        before the swap leaves the engine serving the old epoch."""
        ep = mk()
        ep.publish()
        with ServeEngine(
            ep.index, CUSTOM, RES, ladder=BucketLadder(64, 1024),
            bounds=BBOX, max_wait_s=0.0,
        ) as eng:
            old = ep.index.host
            np.testing.assert_array_equal(
                np.asarray(eng.join(pts, deadline_s=60.0)),
                host_join(pts, old, CUSTOM, RES),
            )
            edit_replace(ep)
            with faults.transient_errors(
                1, sites=("epoch.publish",), exc_factory=BOOM
            ):
                with pytest.raises(RuntimeError, match="synthetic kill"):
                    ep.publish(eng)
            assert ep.epoch == 0  # epochal stayed put...
            np.testing.assert_array_equal(  # ...and so did the engine
                np.asarray(eng.join(pts, deadline_s=60.0)),
                host_join(pts, old, CUSTOM, RES),
            )
            ep.publish(eng)
            assert ep.epoch == 1
            np.testing.assert_array_equal(
                np.asarray(eng.join(pts, deadline_s=60.0)),
                host_join(pts, ep.index.host, CUSTOM, RES),
            )


    def test_publishes_under_live_traffic_answer_an_epoch_each(self, pts):
        """Publishes INTO an engine a client thread keeps querying: no
        request errors, and every answer is one whole epoch's — the
        f64 oracle's of an epoch that was published by the time the
        request returned, never a mix of two."""
        import threading

        ep = mk()
        ep.publish()
        oracles = [host_join(pts, ep.index.host, CUSTOM, RES)]
        answers, errors = [], []
        stop = threading.Event()
        with ServeEngine(
            ep.index, CUSTOM, RES, ladder=BucketLadder(64, 1024),
            bounds=BBOX, max_wait_s=0.0,
        ) as eng:
            eng.warmup()

            def client():
                while not stop.is_set():
                    try:
                        answers.append(
                            np.asarray(eng.join(pts, deadline_s=60.0))
                        )
                    except Exception as e:  # lint: broad-except-ok (the assertion IS that no request errors; collect, don't mask)
                        errors.append(repr(e))
                        return

            t = threading.Thread(target=client, daemon=True)  # lint: thread-context-adoption-ok (load generator: answers only, no telemetry read from this thread)
            t.start()
            for edit in EDITS:
                edit(ep)
                before = len(answers)
                ep.publish(eng)
                oracles.append(host_join(pts, ep.index.host, CUSTOM, RES))
                while len(answers) == before and not errors:
                    stop.wait(0.001)  # traffic flows past every publish
            stop.set()
            t.join(timeout=60)
            # a request in flight at a swap finishes on the epoch it began
            # on; one sent after the last publish is the last epoch's
            final = np.asarray(eng.join(pts, deadline_s=60.0))
        assert not errors, errors[0]
        assert ep.epoch == len(EDITS)
        for got in answers:
            assert any(np.array_equal(got, want) for want in oracles)
        np.testing.assert_array_equal(final, oracles[-1])


# ------------------------------------------------- kill-storm replay


#: (fault site, matching calls let through, epoch the log must replay
#: to). apply's boundaries: pre-tessellate / pre-append / post-append —
#: the delta record is the durable point. publish writes nothing, so
#: both its boundaries (pre-build and the torn swap-vs-counter gap)
#: replay to the applied epoch. compact's boundaries: pre-snapshot /
#: post-snapshot-pre-truncate / post-truncate.
KILL_MATRIX = [
    ("epoch.apply", 0, 0),
    ("epoch.apply", 1, 0),
    ("epoch.apply", 2, 1),
    ("epoch.publish", 0, 1),
    ("epoch.publish", 1, 1),
    ("epoch.compact", 0, 1),
    ("epoch.compact", 1, 1),
    ("epoch.compact", 2, 1),
]


class TestKillReplay:
    @pytest.mark.parametrize("site,skip,survivor", KILL_MATRIX)
    def test_kill_at_every_boundary(self, tmp_path, site, skip, survivor):
        """A kill at ANY fault-site boundary leaves a log that replays
        to a bit-identical index at the surviving epoch."""
        d = tmp_path / "log"
        ep = mk(d)
        with faults.transient_errors(
            1, sites=(site,), skip_first=skip, exc_factory=BOOM
        ):
            with pytest.raises(RuntimeError, match="synthetic kill"):
                edit_replace(ep)
                if site == "epoch.publish":
                    ep.publish()
                elif site == "epoch.compact":
                    ep.compact()
        r = EpochalIndex.replay(str(d), CUSTOM)
        assert r.applied_epoch == survivor and r.epoch == survivor
        assert chip_index_equal(r.index, scratch(r))
        assert len(r) == 3 and list(r._order) == [0, 1, 2]

    def test_torn_publish_never_half_bumps(self, tmp_path):
        """The torn boundary: index swapped, counter not yet bumped. The
        published-epoch counter must NOT have advanced, and replay lands
        cleanly on the durable epoch."""
        d = tmp_path / "log"
        ep = mk(d)
        ep.publish()
        edit_replace(ep)
        with faults.transient_errors(
            1, sites=("epoch.publish",), skip_first=1, exc_factory=BOOM
        ):
            with pytest.raises(RuntimeError, match="synthetic kill"):
                ep.publish()
        assert ep.epoch == 0  # old epoch or a clean replay, never between
        r = EpochalIndex.replay(str(d), CUSTOM)
        assert r.epoch == 1
        assert chip_index_equal(r.index, scratch(r))

    def test_replay_equals_live_instance(self, tmp_path):
        d = tmp_path / "log"
        ep = mk(d)
        for e in EDITS:
            e(ep)
        ep.publish()
        r = EpochalIndex.replay(str(d), CUSTOM)
        assert r.applied_epoch == ep.applied_epoch == 3
        assert r.epoch_token() == ep.epoch_token()
        assert r.series == ep.series and r.chain == ep.chain
        assert chip_index_equal(r.index, ep.index)

    def test_replay_upto_historical_epoch(self, tmp_path):
        """``upto`` stops the replay at a historical epoch — the audit
        knob — and the result matches that epoch's from-scratch build."""
        d = tmp_path / "log"
        ep = mk(d)
        reference = {}
        ep.publish()
        reference[0] = ep.index
        for n, e in enumerate(EDITS, start=1):
            e(ep)
            ep.publish()
            reference[n] = ep.index
        for n in range(4):
            r = EpochalIndex.replay(str(d), CUSTOM, upto=n)
            assert r.applied_epoch == n
            assert chip_index_equal(r.index, reference[n])


# ------------------------------------------------- log refusals


class TestLogRefusals:
    def _logged(self, tmp_path, n_edits=2):
        d = tmp_path / "log"
        ep = mk(d)
        for e in EDITS[:n_edits]:
            e(ep)
        return d, ep

    def test_corrupt_tail_truncates(self, tmp_path):
        """Bit rot / kill-mid-write on the NEWEST delta is tail residue:
        replay truncates it (typed telemetry) and lands on the previous
        epoch, bit-identical."""
        d, _ = self._logged(tmp_path)
        p = d / "delta-00000002.npz"
        p.write_bytes(p.read_bytes()[:-7])
        with telemetry.capture() as events:
            r = EpochalIndex.replay(str(d), CUSTOM)
        assert r.applied_epoch == 1
        assert chip_index_equal(r.index, scratch(r))
        kinds = [
            e for e in events if e["event"] == "epoch_log_truncated"
        ]
        assert kinds and kinds[0]["kind"] == "delta"
        # the truncated record was unlinked: a second replay is clean
        with telemetry.capture() as events:
            EpochalIndex.replay(str(d), CUSTOM, publish=False)
        assert not [
            e for e in events if e["event"] == "epoch_log_truncated"
        ]

    def test_corrupt_interior_refuses_typed(self, tmp_path):
        """A damaged record with VALID successors is not a tail — data
        loss would be silent, so replay refuses typed."""
        d, _ = self._logged(tmp_path)
        p = d / "delta-00000001.npz"
        p.write_bytes(p.read_bytes()[:-7])
        with pytest.raises(EpochLogCorrupt, match="valid successors"):
            EpochalIndex.replay(str(d), CUSTOM)

    def test_missing_interior_epoch_refuses_typed(self, tmp_path):
        d, _ = self._logged(tmp_path)
        (d / "delta-00000001.npz").unlink()
        (d / "delta-00000001.json").unlink()
        with pytest.raises(EpochLogCorrupt, match="missing"):
            EpochalIndex.replay(str(d), CUSTOM)

    def test_forged_chain_refuses_typed(self, tmp_path):
        """A record whose checksum validates but whose ``prev`` does not
        bind to the predecessor is a forged/foreign record — replay
        refuses with the fingerprint mismatch, not a generic error."""
        import hashlib
        import json

        d, _ = self._logged(tmp_path)
        p = d / "delta-00000002.json"
        sidecar = json.loads(p.read_text())
        sidecar["prev"] = "f" * 64
        sidecar["chain"] = hashlib.sha256(
            f"{sidecar['prev']}:{sidecar['sha256']}".encode()
        ).hexdigest()
        p.write_text(json.dumps(sidecar))
        with pytest.raises(EpochFingerprintMismatch, match="chains from"):
            EpochalIndex.replay(str(d), CUSTOM)

    def test_wrong_index_system_refuses_typed(self, tmp_path):
        d, _ = self._logged(tmp_path)

        class OtherSystem(CustomIndexSystem):
            pass

        other = OtherSystem(GridConf(-180, 180, -90, 90, 2, 10.0, 10.0))
        with pytest.raises(EpochFingerprintMismatch, match="index"):
            EpochalIndex.replay(str(d), other)

    def test_empty_or_baseless_log_refuses_typed(self, tmp_path):
        with pytest.raises(EpochLogCorrupt, match="no delta log"):
            EpochalIndex.replay(str(tmp_path / "nothing"), CUSTOM)
        d, _ = self._logged(tmp_path, n_edits=1)
        (d / "base-00000000.npz").write_bytes(b"shredded")
        with pytest.raises(EpochLogCorrupt, match="base record"):
            EpochalIndex.replay(str(d), CUSTOM)


# ------------------------------------------------- compaction


class TestCompaction:
    def test_compact_preserves_identity_and_truncates(self, tmp_path):
        d = tmp_path / "log"
        ep = mk(d)
        edit_replace(ep)
        edit_insert(ep)
        stats = ep.compact()
        assert stats["epoch"] == 2 and stats["truncated"] == 3
        names = sorted(f.name for f in d.iterdir())
        assert names == ["compact-00000002.json", "compact-00000002.npz"]
        ep.publish()
        assert chip_index_equal(ep.index, scratch(ep))
        # the chain is untouched by compaction: a post-compact delta
        # still chains from the last delta's hash, and replay proves it
        edit_remove(ep)
        r = EpochalIndex.replay(str(d), CUSTOM)
        assert r.applied_epoch == 3
        assert chip_index_equal(r.index, scratch(r))
        assert r.series == ep.series  # sealed into the compact record

    def test_log_max_knob_autocompacts(self, tmp_path):
        """MOSAIC_EPOCH_LOG_MAX (here the explicit ``log_max=``, which
        beats the env): once that many deltas accumulate, apply triggers
        compaction-and-truncate with the prefix's fingerprint sealed
        into the snapshot."""
        d = tmp_path / "log"
        ep = mk(d, log_max=2)
        s1 = edit_replace(ep)
        assert "compacted" not in s1
        s2 = edit_insert(ep)
        assert s2["compacted"]["epoch"] == 2
        entries = sorted(f.name for f in d.iterdir())
        assert entries == ["compact-00000002.json", "compact-00000002.npz"]
        edit_remove(ep)  # 1 delta since compact: below the limit again
        assert (d / "delta-00000003.json").exists()
        r = EpochalIndex.replay(str(d), CUSTOM)
        assert r.applied_epoch == 3
        assert chip_index_equal(r.index, scratch(r))

    def test_log_max_env_knob(self, tmp_path, monkeypatch):
        monkeypatch.setenv("MOSAIC_EPOCH_LOG_MAX", "1")
        d = tmp_path / "log"
        ep = mk(d)
        s = edit_replace(ep)
        assert s["compacted"]["epoch"] == 1

    def test_background_compact_adopts_sinks(self, tmp_path):
        d = tmp_path / "log"
        ep = mk(d)
        edit_replace(ep)
        with telemetry.capture() as events:
            t = ep.compact(background=True)
            t.join(timeout=60)
        assert not t.is_alive()
        assert [e for e in events if e["event"] == "epoch_compacted"]
        assert len(ep._blocks) == 1
        ep.publish()
        assert chip_index_equal(ep.index, scratch(ep))

    def test_half_written_compact_falls_back(self, tmp_path):
        """A compact snapshot shredded BEFORE truncation ran (the
        kill-mid-compaction residue) must not poison replay: the base +
        delta prefix still replays the same epoch."""
        d = tmp_path / "log"
        ep = mk(d)
        edit_replace(ep)
        with faults.transient_errors(
            1, sites=("epoch.compact",), skip_first=1, exc_factory=BOOM
        ):
            with pytest.raises(RuntimeError, match="synthetic kill"):
                ep.compact()  # snapshot durable, prefix NOT truncated
        p = d / "compact-00000001.npz"
        p.write_bytes(p.read_bytes()[:-7])
        with telemetry.capture() as events:
            r = EpochalIndex.replay(str(d), CUSTOM)
        assert r.applied_epoch == 1
        assert chip_index_equal(r.index, scratch(r))
        trunc = [e for e in events if e["event"] == "epoch_log_truncated"]
        assert trunc and trunc[0]["kind"] == "compact"


# ------------------------------------------------- durable-stream fence


class TestStreamEpochFence:
    def test_resume_across_epoch_boundary(self, tmp_path):
        """A durable stream run killed mid-flight, with a compaction
        kill AND an epoch advance before anyone resumes: resume against
        the NEW epoch's index refuses typed; resume against the
        snapshot's OWN epoch finishes bit-identical to a clean run."""
        log_dir = tmp_path / "log"
        run_dir = str(tmp_path / "run")
        ep = mk(log_dir)
        ep.publish()
        idx0 = ep.index
        rng = np.random.default_rng(7)
        batches = [
            rng.uniform(BBOX[:2], BBOX[2:], (1024, 2)) for _ in range(3)
        ]
        ring = ring_from_host(batches)
        sj0 = StreamJoin(idx0, CUSTOM, RES, prefetch=True)
        clean = sj0.run(ring, 7, collect=True)
        with faults.inject(
            fail_first=99, skip_first=2, sites=("stream.scan_step",),
            exc_factory=BOOM,
        ):
            with pytest.raises(RuntimeError, match="synthetic kill"):
                sj0.run_durable(
                    ring, 7, run_dir=run_dir, snapshot_every=2,
                    retry_policy=FAST,
                )
        assert checkpoint.list_snapshots(run_dir)
        # the world moves on: an edit lands and a compaction dies
        edit_replace(ep)
        with faults.transient_errors(
            1, sites=("epoch.compact",), skip_first=1, exc_factory=BOOM
        ):
            with pytest.raises(RuntimeError, match="synthetic kill"):
                ep.compact()
        r = EpochalIndex.replay(str(log_dir), CUSTOM)
        assert r.epoch == 1
        # refusal direction: the snapshot is fenced to its epoch
        sj1 = StreamJoin(r.index, CUSTOM, RES, prefetch=True)
        with pytest.raises(EpochFingerprintMismatch, match="epoch"):
            sj1.resume(run_dir, ring, retry_policy=FAST)
        # completion direction: the snapshot's own index finishes the
        # run bit-identically to the clean epoch-0 run
        got = sj0.resume(run_dir, ring, retry_policy=FAST)
        assert (got.checksum, got.matches, got.overflow) == (
            clean.checksum, clean.matches, clean.overflow
        )


# ------------------------------------------------- router epoch advance


def make_router(store, **kw):
    kw.setdefault("program_store", store)
    kw.setdefault("engine_defaults", {
        "ladder": BucketLadder(64, 256),
        "bounds": BBOX,
        "max_wait_s": 0.01,
    })
    return ServeRouter(CUSTOM, **kw)


class TestRouterEpochAdvance:
    def test_advance_updates_tenant_and_metrics(self, tmp_path, pts):
        ep = mk()
        ep.publish()
        with make_router(str(tmp_path / "programs")) as router:
            router.add_tenant("a", ep.index, RES, warm=False)
            edit_replace(ep)
            stats = router.advance_epoch("a", ep)
            assert stats["epoch"] == 1
            m = router.metrics()["tenants"]["a"]
            assert m["epoch"] == 1 and m["epoch_advances"] == 1
            np.testing.assert_array_equal(
                np.asarray(router.join("a", pts)),
                host_join(pts, ep.index.host, CUSTOM, RES),
            )

    def test_failed_advance_keeps_old_snapshot(self, tmp_path, pts):
        """A fault at router.swap mid-advance: the tenant keeps serving
        its current snapshot bit-identically, the tenant's epoch
        accounting is untouched, AND the epochal index stays on its
        previous published epoch."""
        ep = mk()
        ep.publish()
        old_oracle = host_join(pts, ep.index.host, CUSTOM, RES)
        with make_router(str(tmp_path / "programs")) as router:
            router.add_tenant("a", ep.index, RES, warm=False)
            edit_replace(ep)
            with faults.transient_errors(1, sites=("router.swap",)):
                with pytest.raises(TransientDeviceError):
                    router.advance_epoch("a", ep)
            assert ep.epoch == 0
            m = router.metrics()["tenants"]["a"]
            assert m["epoch"] == 0 and m["epoch_advances"] == 0
            np.testing.assert_array_equal(
                np.asarray(router.join("a", pts)), old_oracle
            )
            # the delta log is durable: the retry publishes the epoch
            stats = router.advance_epoch("a", ep)
            assert stats["epoch"] == 1 and ep.epoch == 1
