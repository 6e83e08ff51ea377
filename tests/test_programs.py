"""AOT program-store contract (PR 16): serialized-executable
persistence with the checkpoint discipline — atomic payload-then-sidecar
writes, checksum + environment-fingerprint validation with typed
refusals, zero-compile reload, self-healing re-export, and bit-identical
answers under every failure path — `mosaic_tpu/dispatch/programs.py`."""

import json
import os

import numpy as np
import pytest

from mosaic_tpu.core.geometry import wkt
from mosaic_tpu.core.index import CustomIndexSystem, GridConf
from mosaic_tpu.core.tessellate import tessellate
from mosaic_tpu.runtime import telemetry
from mosaic_tpu.serve import BucketLadder
from mosaic_tpu.dispatch import (
    DispatchCore,
    ProgramFingerprintMismatch,
    ProgramStore,
    ProgramStoreCorrupt,
    backend_fingerprint,
    program_key,
    resolve_program_store,
)
from mosaic_tpu.sql.join import build_chip_index, pip_join

BBOX = (-25.0, -25.0, 35.0, 20.0)
RES = 3
ZONES = [
    "POLYGON ((1 1, 13 2, 12 11, 6 14, 2 9, 1 1))",
    "POLYGON ((-20 -20, -5 -20, -5 -5, -20 -5, -20 -20))",
    "POLYGON ((20 -10, 30 -10, 30 5, 20 5, 20 -10))",
]


@pytest.fixture(scope="module")
def grid():
    return CustomIndexSystem(GridConf(-180, 180, -90, 90, 2, 10.0, 10.0))


@pytest.fixture(scope="module")
def index(grid):
    col = wkt.from_wkt(ZONES)
    return build_chip_index(tessellate(col, grid, RES, keep_core_geoms=False))


LADDER = BucketLadder(64, 256)  # 3 rungs x 2 programs = 6 store entries


def make_core(index, grid, store):
    return DispatchCore(
        index, grid, RES, ladder=LADDER, program_store=store,
    )


def run_core(core, pts):
    padded, n = core.ladder.pad(pts)
    return np.asarray(core.execute_padded(padded))[:n]


@pytest.fixture()
def pts():
    rng = np.random.default_rng(11)
    return rng.uniform(BBOX[:2], BBOX[2:], (100, 2))


# ---------------------------------------------------------------- store

class TestStoreDiscipline:
    def test_roundtrip_and_keys(self, tmp_path):
        store = ProgramStore(str(tmp_path))
        store.save("abc123", b"payload-bytes", meta={"kind": "cells"})
        assert store.load("abc123") == b"payload-bytes"
        assert store.keys() == ["abc123"]

    def test_missing_is_clean_miss(self, tmp_path):
        assert ProgramStore(str(tmp_path)).load("nope") is None
        assert ProgramStore(str(tmp_path / "absent")).keys() == []

    def test_orphan_payload_is_clean_miss(self, tmp_path):
        """A payload without its sidecar is the kill-mid-export remnant:
        invisible to keys() and a miss on load — never half a program."""
        store = ProgramStore(str(tmp_path))
        (tmp_path / "prog-dead.bin").write_bytes(b"partial")
        assert store.load("dead") is None
        assert store.keys() == []

    def test_no_temp_files_survive_save(self, tmp_path):
        store = ProgramStore(str(tmp_path))
        store.save("k", b"x" * 64)
        assert not [n for n in os.listdir(tmp_path) if n.endswith(".tmp")]

    def test_corrupt_payload_typed_refusal(self, tmp_path):
        store = ProgramStore(str(tmp_path))
        store.save("k", b"payload")
        (tmp_path / "prog-k.bin").write_bytes(b"tampered")
        with telemetry.capture() as events:
            with pytest.raises(ProgramStoreCorrupt, match="checksum"):
                store.load("k")
        assert any(
            e.get("event") == "program_store_corrupt_skipped" for e in events
        )

    def test_corrupt_sidecar_typed_refusal(self, tmp_path):
        store = ProgramStore(str(tmp_path))
        store.save("k", b"payload")
        (tmp_path / "prog-k.json").write_text("{not json")
        with pytest.raises(ProgramStoreCorrupt, match="sidecar"):
            store.load("k")

    def test_unknown_version_typed_refusal(self, tmp_path):
        store = ProgramStore(str(tmp_path))
        path = tmp_path / "prog-k.json"
        store.save("k", b"payload")
        sidecar = json.loads(path.read_text())
        sidecar["version"] = 999
        path.write_text(json.dumps(sidecar))
        with pytest.raises(ProgramStoreCorrupt, match="version"):
            store.load("k")

    def test_env_fingerprint_mismatch_typed_refusal(self, tmp_path):
        store = ProgramStore(str(tmp_path))
        path = tmp_path / "prog-k.json"
        store.save("k", b"payload")
        sidecar = json.loads(path.read_text())
        sidecar["env"]["jax"] = "0.0.0-other"
        path.write_text(json.dumps(sidecar))
        with telemetry.capture() as events:
            with pytest.raises(ProgramFingerprintMismatch):
                store.load("k")
        assert any(
            e.get("event") == "program_store_mismatch" for e in events
        )

    def test_program_key_separates_statics(self):
        a = program_key("fp", "join", bucket=64, probe="scatter")
        b = program_key("fp", "join", bucket=128, probe="scatter")
        c = program_key("fp", "cells", bucket=64, probe="scatter")
        d = program_key("fp2", "join", bucket=64, probe="scatter")
        assert len({a, b, c, d}) == 4
        assert a == program_key("fp", "join", probe="scatter", bucket=64)

    def test_backend_fingerprint_shape(self):
        fp = backend_fingerprint()
        assert set(fp) == {"jax", "platform", "device_kind", "device_count"}

    def test_resolve_precedence(self, tmp_path, monkeypatch):
        explicit = ProgramStore(str(tmp_path))
        assert resolve_program_store(explicit) is explicit
        assert resolve_program_store(str(tmp_path)).root == str(tmp_path)
        monkeypatch.setenv("MOSAIC_PROGRAM_STORE", str(tmp_path / "env"))
        assert resolve_program_store(None).root == str(tmp_path / "env")
        monkeypatch.setenv("MOSAIC_PROGRAM_STORE", "")
        assert resolve_program_store(None) is None


# ------------------------------------------------------------- core AOT

class TestCoreAOT:
    def test_export_then_reload_bit_identical(
        self, index, grid, tmp_path, pts
    ):
        """First core exports every rung; a second core warms purely by
        loading, introduces no new executables, and answers exactly the
        batch-path reference."""
        store = str(tmp_path)
        c1 = make_core(index, grid, store)
        w1 = c1.warmup()
        assert w1["aot"] == {"loaded": 0, "exported": 6, "fallback": 0}
        assert len(ProgramStore(store).keys()) == 6

        c2 = make_core(index, grid, store)
        w2 = c2.warmup()
        assert w2["aot"] == {"loaded": 6, "exported": 0, "fallback": 0}
        assert c2.cold_compiles == 0

        ref = np.asarray(
            pip_join(pts, None, grid, RES, chip_index=index, recheck=False)
        )
        np.testing.assert_array_equal(run_core(c1, pts), ref)
        np.testing.assert_array_equal(run_core(c2, pts), ref)

    def test_corrupt_entry_self_heals(self, index, grid, tmp_path, pts):
        """One flipped payload byte: the next core records the typed
        skip, recompiles that program, re-exports it, and the store is
        clean again — answers bit-identical throughout."""
        store = str(tmp_path)
        make_core(index, grid, store).warmup()
        victim = sorted(tmp_path.glob("prog-*.bin"))[0]
        blob = bytearray(victim.read_bytes())
        blob[len(blob) // 2] ^= 0xFF
        victim.write_bytes(bytes(blob))

        with telemetry.capture() as events:
            c = make_core(index, grid, store)
            w = c.warmup()
        assert w["aot"]["loaded"] == 5 and w["aot"]["exported"] == 1
        assert any(
            e.get("event") == "program_store_corrupt_skipped" for e in events
        )
        ref = np.asarray(
            pip_join(pts, None, grid, RES, chip_index=index, recheck=False)
        )
        np.testing.assert_array_equal(run_core(c, pts), ref)

        healed = make_core(index, grid, store).warmup()
        assert healed["aot"] == {"loaded": 6, "exported": 0, "fallback": 0}

    def test_fingerprint_mismatch_falls_back(
        self, index, grid, tmp_path, pts
    ):
        """A sidecar stamped with a foreign environment is REFUSED (not
        loaded — a wrong program could crash or mis-answer) and replaced
        by a fresh compile + export."""
        store = str(tmp_path)
        make_core(index, grid, store).warmup()
        sidecar = sorted(tmp_path.glob("prog-*.json"))[0]
        doc = json.loads(sidecar.read_text())
        doc["env"]["device_count"] = 4096
        sidecar.write_text(json.dumps(doc))

        with telemetry.capture() as events:
            c = make_core(index, grid, store)
            w = c.warmup()
        assert w["aot"]["exported"] == 1
        assert any(
            e.get("event") == "program_store_mismatch" for e in events
        )
        ref = np.asarray(
            pip_join(pts, None, grid, RES, chip_index=index, recheck=False)
        )
        np.testing.assert_array_equal(run_core(c, pts), ref)

    def test_orphan_payload_reexports(self, index, grid, tmp_path):
        """Deleting a sidecar (the state a kill between payload and
        sidecar leaves) is a clean miss: the program recompiles and the
        sidecar is restored."""
        store = str(tmp_path)
        make_core(index, grid, store).warmup()
        sorted(tmp_path.glob("prog-*.json"))[0].unlink()
        w = make_core(index, grid, store).warmup()
        assert w["aot"]["loaded"] == 5 and w["aot"]["exported"] == 1
        assert len(list(tmp_path.glob("prog-*.json"))) == 6

    def test_no_store_no_aot(self, index, grid, monkeypatch):
        monkeypatch.delenv("MOSAIC_PROGRAM_STORE", raising=False)
        core = DispatchCore(index, grid, RES, ladder=LADDER)
        assert core._programs is None
        w = core.warmup()
        assert "aot" not in w


#: one serve lifetime in a process of its own: build the SAME index
#: (the tessellation fingerprint is the store key), warm from the store,
#: answer a fixed probe set, print one JSON line
_CHILD = """
import hashlib, json, sys
import numpy as np
from mosaic_tpu.core.geometry import wkt
from mosaic_tpu.core.index import CustomIndexSystem, GridConf
from mosaic_tpu.core.tessellate import tessellate
from mosaic_tpu.dispatch import DispatchCore, backend_compiles
from mosaic_tpu.serve import BucketLadder
from mosaic_tpu.sql.join import build_chip_index

grid = CustomIndexSystem(GridConf(-180, 180, -90, 90, 2, 10.0, 10.0))
col = wkt.from_wkt(json.loads(sys.argv[2]))
index = build_chip_index(tessellate(col, grid, 3, keep_core_geoms=False))
core = DispatchCore(
    index, grid, 3, ladder=BucketLadder(64, 256), program_store=sys.argv[1]
)
n0 = backend_compiles()
warm = core.warmup()
pts = np.random.default_rng(11).uniform((-25, -25), (35, 20), (100, 2))
padded, n = core.ladder.pad(pts)
out = np.asarray(core.execute_padded(padded))[:n]
print(json.dumps({
    "aot": warm["aot"], "cold_compiles": core.cold_compiles,
    "backend_compiles": backend_compiles() - n0,
    "sha": hashlib.sha256(out.astype(np.int64).tobytes()).hexdigest(),
}))
"""


class TestRelaunchedProcess:
    def test_a_child_process_warms_from_the_store_and_compiles_nothing(
        self, index, grid, tmp_path, pts
    ):
        """A REAL relaunch: no in-memory executable cache can stand in
        for the store. The first child exports every rung; the second
        loads them all, exports none, compiles nothing, and both answer
        this process's reference bit for bit."""
        import hashlib
        import subprocess
        import sys

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo)
        env.pop("MOSAIC_PROGRAM_STORE", None)

        def child():
            done = subprocess.run(
                [sys.executable, "-c", _CHILD, str(tmp_path),
                 json.dumps(ZONES)], env=env,
                capture_output=True, text=True, timeout=300,
            )
            assert done.returncode == 0, done.stderr[-2000:]
            return json.loads(done.stdout.strip().splitlines()[-1])

        cold, warm = child(), child()
        assert cold["aot"] == {"loaded": 0, "exported": 6, "fallback": 0}
        assert warm["aot"] == {"loaded": 6, "exported": 0, "fallback": 0}
        assert warm["cold_compiles"] == 0
        assert warm["backend_compiles"] == 0
        ref = np.asarray(
            pip_join(pts, None, grid, RES, chip_index=index, recheck=False)
        )
        want = hashlib.sha256(ref.astype(np.int64).tobytes()).hexdigest()
        assert cold["sha"] == warm["sha"] == want


# --------------------------------------------------- epochal provenance

class TestEpochPrograms:
    """ISSUE 18 regression: the store key must fold in the index's
    EPOCH identity, not just its cell fingerprint — two epochs can
    cover the exact same cells with different chip geometry, and a
    stale program answering for the wrong epoch is silent corruption."""

    ZONES = [
        "POLYGON ((1 1, 13 2, 12 11, 6 14, 2 9, 1 1))",
        "POLYGON ((-20 -20, -5 -20, -5 -5, -20 -5, -20 -20))",
        "POLYGON ((20 -10, 30 -10, 30 5, 20 5, 20 -10))",
    ]
    #: zone 0 with one vertex nudged INSIDE its cells: the covered cell
    #: set is unchanged, the chip geometry is not
    ZONE0_NUDGED = "POLYGON ((1 1, 13 2.001, 12 11, 6 14, 2 9, 1 1))"

    def _epochal(self, grid):
        from mosaic_tpu.core.geometry import wkt as _wkt
        from mosaic_tpu.index import EpochalIndex

        ep = EpochalIndex(
            _wkt.from_wkt(self.ZONES), grid, RES, keep_core_geoms=False
        )
        ep.publish()
        return ep

    def test_new_epoch_same_cells_never_loads_stale(
        self, grid, tmp_path
    ):
        """Stale direction: a geometry edit that keeps the cell set
        identical still changes the program identity — the new epoch
        must export fresh programs, never load epoch-0's — and warmup
        GCs the superseded epoch's entries."""
        from mosaic_tpu.core.geometry import wkt as _wkt
        from mosaic_tpu.runtime import checkpoint

        ep = self._epochal(grid)
        idx0 = ep.index
        store = str(tmp_path)
        w0 = make_core(idx0, grid, store).warmup()
        assert w0["aot"] == {"loaded": 0, "exported": 6, "fallback": 0}
        assert w0["aot_gc"] == 0

        ep.apply(upsert=_wkt.from_wkt([self.ZONE0_NUDGED]), ids=[0])
        ep.publish()
        idx1 = ep.index
        # the collision this regression pins: same cells, new epoch
        np.testing.assert_array_equal(
            np.asarray(idx0.cells), np.asarray(idx1.cells)
        )
        assert checkpoint.index_identity(idx0) != \
            checkpoint.index_identity(idx1)

        with telemetry.capture() as events:
            w1 = make_core(idx1, grid, store).warmup()
        assert w1["aot"] == {"loaded": 0, "exported": 6, "fallback": 0}
        assert w1["aot_gc"] == 6  # epoch-0 ladder dropped
        assert len(ProgramStore(store).keys()) == 6
        assert any(
            e.get("event") == "program_store_gc" for e in events
        )

    def test_same_epoch_reload_is_stable(self, grid, tmp_path):
        """Stability direction: re-warming the SAME epoch is a pure
        load — no re-export, no GC thrash."""
        ep = self._epochal(grid)
        store = str(tmp_path)
        make_core(ep.index, grid, store).warmup()
        w = make_core(ep.index, grid, store).warmup()
        assert w["aot"] == {"loaded": 6, "exported": 0, "fallback": 0}
        assert w["aot_gc"] == 0
        assert len(ProgramStore(store).keys()) == 6

    def test_gc_spares_other_series_and_unstamped(self, grid, tmp_path):
        """gc_superseded only touches entries of the SAME series with an
        OLDER epoch: plain (unstamped) indexes and foreign series
        survive an epoch advance untouched."""
        store = ProgramStore(str(tmp_path))
        store.save("plain", b"x", meta={"kind": "cells"})
        store.save("other", b"y", meta={
            "index_series": "someoneelse", "index_epoch": 0,
        })
        store.save("mine-old", b"z", meta={
            "index_series": "s1", "index_epoch": 0,
        })
        store.save("mine-new", b"w", meta={
            "index_series": "s1", "index_epoch": 3,
        })
        assert store.gc_superseded("s1", 3) == 1
        assert store.keys() == ["mine-new", "other", "plain"]
