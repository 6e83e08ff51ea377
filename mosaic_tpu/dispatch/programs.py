"""AOT compiled-program persistence: the zero-cold-start restart store.

Every process restart used to pay the full warmup compile storm before
the first request was admitted. This module makes the compiled
executables themselves a durable artifact, the same way `tune/store.py`
made knob recommendations one: each ladder rung's cells program and
join program is lowered once (`jax.jit(...).lower(...).compile()`),
serialized via `jax.experimental.serialize_executable`, and persisted
next to the tune profiles with the checkpoint discipline —

- one program = one ``prog-<key>.bin`` payload plus one
  ``prog-<key>.json`` sidecar carrying the payload's SHA-256 and the
  environment fingerprint. Both are written temp-first and
  ``os.replace``\\ d, payload BEFORE sidecar, so a kill mid-export
  leaves an orphaned payload (a cache miss), never a half-written
  program under a valid name;
- the **key** is a digest of the restart-stable program identity: the
  index's tessellation fingerprint (`tune.store.index_fingerprint` —
  NOT ``id(index)``, which `dispatch_signature` uses for its in-process
  key), the bucket, resolution, and every static argument of the
  lowering;
- the sidecar records the **environment fingerprint** (jax version,
  backend platform, device kind/count). Loading under a different
  fingerprint raises the typed :class:`ProgramFingerprintMismatch`; a
  damaged payload or sidecar raises :class:`ProgramStoreCorrupt`. Both
  are REFUSALS the dispatch core answers by falling back to plain
  compilation (and re-exporting) — never a wrong program, never a
  crash.

The PyTreeDefs `serialize` returns are deliberately NOT persisted:
pickled treedefs bind to the pickling process's pytree registrations.
They are reconstructed at load time from the live call prototypes
(`jax.tree_util.tree_structure` over the same ``((args), {})`` the
lowering saw), so a payload loads iff the live index and statics
produce the exact structure it was built for — one more guard, for
free, on top of the key.

Knob: ``MOSAIC_PROGRAM_STORE`` names the store directory (explicit
``program_store=`` argument beats it, per the repo-wide precedence).
"""

from __future__ import annotations

import hashlib
import json
import os

import numpy as np

from ..runtime import telemetry as _telemetry
from ..runtime.errors import MosaicRuntimeError

VERSION = 1


class ProgramStoreCorrupt(MosaicRuntimeError):
    """A persisted program failed validation (unparseable sidecar,
    unknown format version, payload checksum mismatch). The caller must
    fall back to plain compilation; the next export self-heals the
    entry."""


class ProgramFingerprintMismatch(MosaicRuntimeError):
    """The persisted program was built under a DIFFERENT environment
    fingerprint (jax version / backend / device topology) — loading it
    could execute a wrong or crashing program, so this is a refusal.
    Fall back to plain compilation and re-export."""


def backend_fingerprint() -> dict:
    """The environment identity a serialized executable binds to: a
    payload is only loadable under the exact jax version and device
    topology that produced it."""
    import jax

    dev = jax.devices()[0]
    return {
        "jax": jax.__version__,
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", ""),
        "device_count": jax.device_count(),
    }


def program_key(index_fingerprint: str, kind: str, **statics) -> str:
    """Stable content key for one program: sha256 over the canonical
    JSON of the tessellation fingerprint, the program kind (``cells`` /
    ``join``), and every static argument of the lowering."""
    body = {
        "index": index_fingerprint,
        "kind": kind,
        "statics": {k: statics[k] for k in sorted(statics)},
    }
    return hashlib.sha256(
        json.dumps(body, sort_keys=True, default=str).encode()
    ).hexdigest()[:32]


def resolve_program_store(program_store):
    """Host-side resolution of the store argument: an explicit
    :class:`ProgramStore` or path wins; otherwise the
    ``MOSAIC_PROGRAM_STORE`` env knob; otherwise None (AOT persistence
    off)."""
    if program_store is None:
        raw = os.environ.get("MOSAIC_PROGRAM_STORE", "").strip()
        if not raw:
            return None
        return ProgramStore(raw)
    if isinstance(program_store, ProgramStore):
        return program_store
    return ProgramStore(str(program_store))


class ProgramStore:
    """Serialized-executable versions under one directory
    (conventionally next to the index artifacts and tune profiles)."""

    def __init__(self, root: str):
        self.root = str(root)

    def _paths(self, key: str) -> tuple[str, str]:
        base = os.path.join(self.root, f"prog-{key}")
        return base + ".bin", base + ".json"

    def keys(self) -> list[str]:
        """Persisted program keys (validity unchecked): sidecar-backed
        entries only — an orphaned payload is a kill-mid-export remnant,
        not a program."""
        try:
            names = os.listdir(self.root)
        except FileNotFoundError:
            return []
        return sorted(
            n[len("prog-"):-len(".json")]
            for n in names
            if n.startswith("prog-") and n.endswith(".json")
        )

    def save(self, key: str, payload: bytes, meta: dict | None = None) -> str:
        """Persist one serialized executable; returns the sidecar path.

        Atomic per file, payload FIRST: a sidecar's existence implies a
        complete payload was on disk at write time (the same ordering
        `runtime/checkpoint.py` uses for its npz + json pair)."""
        os.makedirs(self.root, exist_ok=True)
        bin_path, json_path = self._paths(key)
        tmp = bin_path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(payload)
        os.replace(tmp, bin_path)
        sidecar = {
            "version": VERSION,
            "key": key,
            "sha256": hashlib.sha256(payload).hexdigest(),
            "env": backend_fingerprint(),
            # which process exported this program — fleet_report joins
            # sidecars to trails by this id across a restart storm
            "incarnation": _telemetry.INCARNATION,
            "meta": meta or {},
        }
        tmp = json_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(sidecar, f, sort_keys=True, indent=1)
        os.replace(tmp, json_path)
        _telemetry.record(
            "program_store_saved", root=self.root, key=key,
            nbytes=len(payload), **_flat_meta(meta),
        )
        return json_path

    def load(self, key: str) -> "bytes | None":
        """The payload for ``key``, or None on a clean miss (no sidecar
        — including the orphaned-payload state a kill mid-export
        leaves).

        Raises :class:`ProgramFingerprintMismatch` when the entry was
        built under a different environment fingerprint, and
        :class:`ProgramStoreCorrupt` when the sidecar or payload fails
        validation — both after recording the typed telemetry event, so
        a fleet can chart refusals without scraping logs."""
        bin_path, json_path = self._paths(key)
        try:
            with open(json_path) as f:
                sidecar = json.load(f)
        except FileNotFoundError:
            return None
        except (OSError, ValueError) as e:
            self._corrupt(key, f"unreadable sidecar: {e!r}")
        if sidecar.get("version") != VERSION:
            self._corrupt(
                key, f"unknown format version {sidecar.get('version')!r}"
            )
        env = backend_fingerprint()
        if sidecar.get("env") != env:
            _telemetry.record(
                "program_store_mismatch", root=self.root, key=key,
                stored=json.dumps(sidecar.get("env"), sort_keys=True),
                current=json.dumps(env, sort_keys=True),
            )
            raise ProgramFingerprintMismatch(
                f"program {key} under {self.root!r} was built for "
                f"{sidecar.get('env')!r}, not the current environment "
                f"{env!r} — falling back to plain compilation"
            )
        try:
            with open(bin_path, "rb") as f:
                payload = f.read()
        except OSError as e:
            self._corrupt(key, f"unreadable payload: {e!r}")
        if hashlib.sha256(payload).hexdigest() != sidecar.get("sha256"):
            self._corrupt(key, "payload checksum mismatch")
        _telemetry.record(
            "program_store_loaded", root=self.root, key=key,
            nbytes=len(payload),
        )
        return payload

    def gc_superseded(self, series: str, keep_epoch: int) -> int:
        """Drop every entry persisted for an EARLIER epoch of the same
        index series (sidecar meta ``index_series``/``index_epoch``,
        stamped by the dispatch core when its index carries an epoch).

        Superseded entries are dead weight by construction — the epoch
        token is part of their key, so they can never be loaded again —
        but without GC a mutating index grows the store by one ladder of
        programs per epoch. Entries from other series, from the current
        (or a newer) epoch, or without epoch provenance are untouched.
        Sidecar is unlinked FIRST so a kill mid-GC leaves an orphaned
        payload (a cache miss), never a sidecar pointing at nothing.
        """
        removed = 0
        for key in self.keys():
            bin_path, json_path = self._paths(key)
            try:
                with open(json_path) as f:
                    sidecar = json.load(f)
            except (OSError, ValueError):
                continue  # unreadable entries are load's problem, not GC's
            meta = sidecar.get("meta") or {}
            if meta.get("index_series") != series:
                continue
            try:
                entry_epoch = int(meta["index_epoch"])
            except (KeyError, TypeError, ValueError):
                continue
            if entry_epoch >= int(keep_epoch):
                continue
            for path in (json_path, bin_path):
                try:
                    os.unlink(path)
                except FileNotFoundError:
                    pass
            removed += 1
        if removed:
            _telemetry.record(
                "program_store_gc", root=self.root, series=series[:16],
                keep_epoch=int(keep_epoch), removed=removed,
            )
        return removed

    def _corrupt(self, key: str, why: str):
        _telemetry.record(
            "program_store_corrupt_skipped", root=self.root, key=key,
            error=why[:200],
        )
        raise ProgramStoreCorrupt(
            f"program {key} under {self.root!r} failed validation "
            f"({why}) — falling back to plain compilation"
        )


def _flat_meta(meta: dict | None) -> dict:
    out = {}
    for k, v in (meta or {}).items():
        if isinstance(v, (int, float, bool, str, type(None))):
            out[f"meta_{k}"] = v
    return out


# ------------------------------------------------- core program bundles

def serialize_compiled(compiled) -> bytes:
    """Payload bytes of one compiled executable (treedefs dropped — see
    module docstring)."""
    from jax.experimental import serialize_executable as _se

    payload, _, _ = _se.serialize(compiled)
    return payload


def deserialize_compiled(
    payload: bytes, example_args: tuple, out_aval, devices
):
    """Reload a payload as a callable, reconstructing the in/out
    PyTreeDefs from the live prototypes the lowering saw.

    ``devices`` are the devices the program was compiled for: left
    unset, `deserialize_and_load` binds the executable to EVERY local
    device, and a one-device program then refuses its one-shard
    arguments on a multi-device host."""
    from jax.experimental import serialize_executable as _se
    from jax.tree_util import tree_structure

    in_tree = tree_structure((tuple(example_args), {}))
    out_tree = tree_structure(out_aval)
    return _se.deserialize_and_load(
        payload, in_tree, out_tree, execution_devices=list(devices)
    )


def core_program_statics(core, bucket: int, kind: str) -> dict:
    """The restart-stable static identity of one of a
    :class:`~mosaic_tpu.dispatch.core.DispatchCore`'s per-bucket
    programs — everything `dispatch_signature` keys on, with the
    process-local ``id(index)`` replaced by the tessellation
    fingerprint (done by the caller) and the trace-relevant dtypes
    pinned."""
    fcap, hcap, ccap = core.caps(bucket)
    statics = {
        "bucket": int(bucket),
        "resolution": core.resolution,
        "dtype": str(np.dtype(core._dtype)),
        "cell_dtype": str(core.cell_dtype) if core.cell_dtype else None,
    }
    if kind == "join":
        statics.update(
            writeback=core.writeback, probe=core.probe,
            found_cap=fcap, heavy_cap=hcap, convex_cap=ccap,
        )
    return statics
