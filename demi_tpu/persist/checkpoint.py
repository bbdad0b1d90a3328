"""Crash-safe, versioned snapshots of exploration state.

Layout of a checkpoint directory (one ``CheckpointStore`` root):

    <root>/
      ckpt-000001/
        MANIFEST.json        format version, meta, per-section digests
        <section>.json       one structural-JSON payload per section
      ckpt-000002/
      ...

Write protocol: every section is written into ``ckpt-N.tmp/`` and
fsynced, the manifest (carrying each section's sha256 + byte count) is
written last, the temp directory itself is fsynced, then renamed into
place and the root directory fsynced — a crash at ANY point leaves
either the previous generations untouched or a ``.tmp`` directory the
loader never looks at. The last ``keep`` generations are retained, so a
snapshot corrupted after the fact (torn disk, bit rot, a hostile test)
degrades to the previous good one: ``load_latest`` walks newest→oldest,
verifying the manifest version and every section digest, and counts each
rejected generation in ``persist.corrupt_fallbacks`` (warn once per
generation, never crash — worst case the run restarts from scratch,
which is exactly today's behavior).

Payload codecs: the mutable search state of ``DeviceDPOR`` (frontier,
explored tuple/digest sets, sleep rows, class keys, wakeup guides,
violation codes, rng round counters), the host ``DPORScheduler``
(dep-graph records, backtrack heap, sleep ledgers), and the
``ExplorationController`` (weight-tuner coordinates, corpus fingerprint
set) all round-trip through structural JSON — ints, nested lists, hex
strings — so a restored run continues bit-identically (pinned by
tests/test_persist.py). Rounds are generation-frozen and deterministic
in this state, which is what makes a round-boundary snapshot a complete
resume point.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import sys
import time
from typing import Any, Dict, List, NamedTuple, Optional

from .. import obs

#: Bump when a payload's schema changes incompatibly. A loader never
#: accepts a NEWER version than it was built for (it cannot know the
#: schema); older-but-valid generations keep loading.
FORMAT_VERSION = 1

_MANIFEST = "MANIFEST.json"


class CheckpointMismatch(ValueError):
    """A checkpoint's recorded workload shape does not match the object
    it is being restored into (different app, batch size, sleep mode...):
    restoring would silently explore a different space, so refuse."""


class Checkpoint(NamedTuple):
    generation: int
    meta: Dict[str, Any]
    sections: Dict[str, Any]
    path: str


def _warn(msg: str) -> None:
    print(f"demi_tpu.persist: {msg}", file=sys.stderr)


class CheckpointStore:
    """Atomic, generation-versioned snapshot store (see module doc)."""

    def __init__(self, root: str, keep: int = 3):
        if keep < 1:
            raise ValueError("keep must be >= 1")
        self.root = root
        self.keep = keep
        # Local ledger mirrored into persist.* obs series (force-written:
        # durability events are rare and load-bearing).
        self.stats: Dict[str, int] = {
            "snapshots_written": 0,
            "snapshot_bytes": 0,
            "restore_hits": 0,
            "corrupt_fallbacks": 0,
        }

    # -- write -------------------------------------------------------------
    def save(self, sections: Dict[str, Any], meta: Dict[str, Any]) -> str:
        """Write one snapshot generation atomically; returns its path."""
        os.makedirs(self.root, exist_ok=True)
        gen = self._next_generation()
        name = f"ckpt-{gen:06d}"
        tmp = os.path.join(self.root, name + ".tmp")
        final = os.path.join(self.root, name)
        if os.path.isdir(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp)
        manifest: Dict[str, Any] = {
            "format_version": FORMAT_VERSION,
            "generation": gen,
            "created": time.strftime("%Y-%m-%dT%H:%M:%S"),
            "meta": meta,
            "sections": {},
        }
        total = 0
        for sname in sorted(sections):
            data = json.dumps(
                sections[sname], sort_keys=True, separators=(",", ":")
            ).encode()
            self._write_fsync(os.path.join(tmp, sname + ".json"), data)
            manifest["sections"][sname] = {
                "sha256": hashlib.sha256(data).hexdigest(),
                "bytes": len(data),
            }
            total += len(data)
        mdata = json.dumps(manifest, sort_keys=True, indent=1).encode()
        self._write_fsync(os.path.join(tmp, _MANIFEST), mdata)
        total += len(mdata)
        self._fsync_dir(tmp)
        os.rename(tmp, final)
        self._fsync_dir(self.root)
        self.stats["snapshots_written"] += 1
        self.stats["snapshot_bytes"] += total
        obs.counter("persist.snapshots_written").force_inc()
        obs.counter("persist.snapshot_bytes").force_inc(total)
        self._prune()
        return final

    @staticmethod
    def _write_fsync(path: str, data: bytes) -> None:
        with open(path, "wb") as f:
            f.write(data)
            f.flush()
            os.fsync(f.fileno())

    @staticmethod
    def _fsync_dir(path: str) -> None:
        try:
            fd = os.open(path, os.O_RDONLY)
        except OSError:
            return  # platform without directory fds: rename is still atomic
        try:
            os.fsync(fd)
        except OSError:
            pass
        finally:
            os.close(fd)

    def _next_generation(self) -> int:
        gens = self.generations()
        return (gens[-1] if gens else 0) + 1

    def _prune(self) -> None:
        gens = self.generations()
        for g in gens[: -self.keep]:
            shutil.rmtree(
                os.path.join(self.root, f"ckpt-{g:06d}"), ignore_errors=True
            )
        # Stale .tmp dirs from a crashed writer are dead weight (the
        # loader never reads them); clear any not belonging to a live
        # write (ours was renamed away already).
        try:
            entries = os.listdir(self.root)
        except OSError:
            return
        for e in entries:
            if e.startswith("ckpt-") and e.endswith(".tmp"):
                shutil.rmtree(os.path.join(self.root, e), ignore_errors=True)

    # -- read --------------------------------------------------------------
    def generations(self) -> List[int]:
        """Generation numbers present on disk, oldest first (completed
        renames only — ``.tmp`` writes are invisible)."""
        try:
            entries = os.listdir(self.root)
        except OSError:
            return []
        out = []
        for e in entries:
            if e.startswith("ckpt-") and not e.endswith(".tmp"):
                try:
                    out.append(int(e[len("ckpt-"):]))
                except ValueError:
                    continue
        return sorted(out)

    def load_latest(self) -> Optional[Checkpoint]:
        """Newest generation that validates (manifest version + every
        section digest); corrupt generations are warned about, counted,
        and skipped — degrade, never crash. None when nothing loads."""
        for gen in reversed(self.generations()):
            path = os.path.join(self.root, f"ckpt-{gen:06d}")
            try:
                ckpt = self._load_one(gen, path)
            except Exception as exc:
                self.stats["corrupt_fallbacks"] += 1
                obs.counter("persist.corrupt_fallbacks").force_inc()
                _warn(
                    f"checkpoint {path!r} unusable ({exc}); falling back "
                    "to the previous generation"
                )
                continue
            self.stats["restore_hits"] += 1
            obs.counter("persist.restore_hits").force_inc()
            return ckpt
        return None

    def _load_one(self, gen: int, path: str) -> Checkpoint:
        with open(os.path.join(path, _MANIFEST), "rb") as f:
            manifest = json.loads(f.read())
        version = manifest.get("format_version")
        if not isinstance(version, int) or version > FORMAT_VERSION:
            raise ValueError(
                f"format version {version!r} is newer than this build's "
                f"{FORMAT_VERSION}"
            )
        sections: Dict[str, Any] = {}
        for sname, rec in manifest.get("sections", {}).items():
            spath = os.path.join(path, sname + ".json")
            with open(spath, "rb") as f:
                data = f.read()
            digest = hashlib.sha256(data).hexdigest()
            if digest != rec.get("sha256") or len(data) != rec.get("bytes"):
                raise ValueError(f"section {sname!r} digest mismatch")
            sections[sname] = json.loads(data)
        return Checkpoint(
            generation=gen,
            meta=manifest.get("meta", {}),
            sections=sections,
            path=path,
        )


# ---------------------------------------------------------------------------
# Structural-JSON helpers (tuples <-> lists, bytes <-> hex)
# ---------------------------------------------------------------------------

def _tt(obj):
    """Deep list -> tuple (the inverse of JSON's tuple flattening):
    prescriptions, class keys, and guide rows are all nested int tuples."""
    if isinstance(obj, list):
        return tuple(_tt(x) for x in obj)
    return obj


def _b64(data: bytes) -> str:
    import base64

    return base64.b64encode(data).decode("ascii")


def _unb64(s: str) -> bytes:
    import base64

    return base64.b64decode(s.encode("ascii"))


def _pack_rows(items) -> Dict[str, Any]:
    """Pack an ordered list of prescriptions (tuples of fixed-width int
    rows) into base64 int32 blobs: per-item row counts + the rows
    concatenated. At soak scale the explored set is tens of MB of
    records; as nested JSON lists it was ~5x bigger and its
    serialization/parse time dominated both snapshot wall time and
    time-to-resume, so the bulk sections ride this binary form inside
    the (still structural-JSON) section files."""
    import numpy as np

    items = list(items)
    lens = np.asarray([len(p) for p in items], np.int32)
    all_rows = [r for p in items for r in p]
    if all_rows:
        flat = np.asarray(all_rows, np.int32)
        w = int(flat.shape[1])
        rows_b = flat.tobytes()
    else:
        w = 0
        rows_b = b""
    return {
        "n": len(items), "w": w,
        "lens": _b64(lens.tobytes()), "rows": _b64(rows_b),
    }


def _unpack_rows(obj: Dict[str, Any]) -> List[tuple]:
    import numpy as np

    lens = np.frombuffer(_unb64(obj["lens"]), np.int32)
    w = int(obj["w"])
    if w:
        flat = np.frombuffer(_unb64(obj["rows"]), np.int32).reshape(-1, w)
        row_tuples = list(map(tuple, flat.tolist()))
    else:
        row_tuples = []
    out: List[tuple] = []
    off = 0
    for m in lens.tolist():
        out.append(tuple(row_tuples[off:off + m]))
        off += m
    return out


def _pack_ints(values) -> str:
    import numpy as np

    return _b64(np.asarray(list(values), np.int64).tobytes())


def _unpack_ints(s: str) -> List[int]:
    import numpy as np

    return np.frombuffer(_unb64(s), np.int64).tolist()


def _pack_digests(items) -> str:
    """Sorted fixed-width digest set as one blob (16-byte content keys)."""
    return _b64(b"".join(sorted(items)))


def _unpack_digests(s: str, size: int = 16) -> set:
    buf = _unb64(s)
    return {buf[i:i + size] for i in range(0, len(buf), size)}


# ---------------------------------------------------------------------------
# Fleet wire codecs: the delta-encoded zlib frames the explored-log
# sections already ride, exposed as standalone payloads so frontier
# deltas, round leases, and class-ledger segments cross the DCN in the
# exact on-disk format (demi_tpu/fleet).
# ---------------------------------------------------------------------------

def pack_prescriptions(items) -> Dict[str, Any]:
    """One delta-encoded zlib frame over an ordered list of row-tuple
    sequences (prescriptions OR Mazurkiewicz class keys — any nested
    int-tuple rows of one fixed width). Deterministic bytes for a given
    input order, which is what makes the fleet's content-addressed
    class-store segments self-verifying."""
    items = list(items)
    frame, w, _last = _encode_explored_frame(items, (), 0)
    return {"n": len(items), "w": w, "frames": [_b64(frame)]}


def unpack_prescriptions(obj: Dict[str, Any]) -> List[tuple]:
    """Inverse of ``pack_prescriptions``."""
    return _decode_explored_frames(obj["frames"])


def pack_array(a) -> Dict[str, Any]:
    """zlib-compressed ndarray payload (shape + dtype + bytes) — the
    lease/result codec for kernel inputs and harvested lane records
    (trace blocks are highly regular; level-1 zlib shrinks them ~10x)."""
    import zlib

    import numpy as np

    a = np.ascontiguousarray(np.asarray(a))
    return {
        "shape": list(a.shape),
        "dtype": str(a.dtype),
        "z": _b64(zlib.compress(a.tobytes(), 1)),
    }


def unpack_array(obj: Dict[str, Any]):
    """Inverse of ``pack_array``."""
    import zlib

    import numpy as np

    buf = zlib.decompress(_unb64(obj["z"]))
    return (
        np.frombuffer(buf, dtype=obj["dtype"]).reshape(obj["shape"]).copy()
    )


# ---------------------------------------------------------------------------
# DeviceDPOR payload
# ---------------------------------------------------------------------------

def _code_digest(h, v, depth: int = 0) -> None:
    """Fold one closure/constant value into a handler fingerprint,
    deterministically across processes: simple scalars by repr, arrays
    by bytes, functions by bytecode (addresses never reach the hash)."""
    import numpy as np

    if isinstance(v, (int, float, str, bool, bytes, type(None))):
        h.update(repr(v).encode())
    elif isinstance(v, np.ndarray):
        h.update(v.tobytes())
    elif isinstance(v, (tuple, list)) and depth < 3:
        for x in v:
            _code_digest(h, x, depth + 1)
    elif callable(v) and hasattr(v, "__code__"):
        h.update(v.__code__.co_code)
        for cell in v.__closure__ or ():
            try:
                _code_digest(h, cell.cell_contents, depth + 1)
            except ValueError:
                pass
    else:
        h.update(type(v).__name__.encode())


def handler_fingerprint(app) -> str:
    """Identity of the app's BEHAVIOR (handler/invariant/init bytecode +
    simple closure constants): ``DSLApp.name`` is only the actor-name
    prefix, so two same-shape apps with different handlers — raft with
    and without a seeded bug — would otherwise pass the workload check
    and silently restore each other's frontiers (the same collision the
    tuning-cache discriminator documents)."""
    h = hashlib.sha256()
    for fn in (app.handler, app.invariant, app.init_state):
        if fn is not None:
            _code_digest(h, fn)
    if getattr(app, "kept_words", ()):
        # What a restart keeps is behaviour too; an app that names no
        # durable word keeps the fingerprint it had.
        h.update(repr(tuple(app.kept_words)).encode())
    if getattr(app, "channels", "any") != "any":
        # So is the order its network keeps.
        h.update(app.channels.encode())
    return h.hexdigest()[:16]


def device_dpor_workload(dpor) -> Dict[str, Any]:
    """The shape discriminator a restore refuses to cross: fields that
    change what a prescription means or how rounds derive."""
    return {
        "handler": handler_fingerprint(dpor.app),
        "app": dpor.app.name,
        "actors": int(dpor.app.num_actors),
        "rec_width": int(dpor.cfg.rec_width),
        "max_steps": int(dpor.cfg.max_steps),
        "pool": int(dpor.cfg.pool_capacity),
        "batch_size": int(dpor.batch_size),
        "key_mode": dpor.key_mode,
        "sleep": dpor.sleep is not None,
        "static": dpor.static_independence is not None,
    }


def _lcp(a: tuple, b: tuple) -> int:
    """Longest common row-prefix of two prescriptions. Sibling
    prescriptions derived from the same lane share row-tuple OBJECTS
    (the deriver materializes one row list per lane), so the common
    case is an identity hit per row, not a 12-int comparison."""
    n = min(len(a), len(b))
    i = 0
    while i < n and (a[i] is b[i] or a[i] == b[i]):
        i += 1
    return i


def _encode_explored_frame(items, prev: tuple, w_expect: int):
    """One delta frame of the explored log: each prescription encoded
    as (lcp with the PREVIOUS log entry, its suffix rows) — admission
    order is lane-major pair order, so consecutive entries share long
    prefixes and the O(n*depth) row explosion collapses to near-linear
    — then zlib-compressed (the suffixes are still highly regular).
    Returns ``(frame_bytes, w, last_entry)``."""
    import zlib

    import numpy as np

    lcps = []
    slens = []
    suffix_rows = []
    w = w_expect
    for p in items:
        k = _lcp(prev, p)
        lcps.append(k)
        slens.append(len(p) - k)
        suffix_rows.extend(p[k:])
        prev = p
    if suffix_rows:
        flat = np.asarray(suffix_rows, np.int32)
        if w and int(flat.shape[1]) != w:
            raise ValueError("mixed prescription row widths")
        w = int(flat.shape[1])
        rows_b = flat.tobytes()
    else:
        rows_b = b""
    head = np.asarray([len(lcps), w], np.int32).tobytes()
    body = (
        head
        + np.asarray(lcps, np.int32).tobytes()
        + np.asarray(slens, np.int32).tobytes()
        + rows_b
    )
    return zlib.compress(body, 1), w, prev


def _decode_explored_frames(frames) -> List[tuple]:
    import zlib

    import numpy as np

    out: List[tuple] = []
    prev: tuple = ()
    for fb in frames:
        buf = zlib.decompress(_unb64(fb))
        n, fw = np.frombuffer(buf[:8], np.int32).tolist()
        off = 8
        lcps = np.frombuffer(buf[off:off + 4 * n], np.int32).tolist()
        off += 4 * n
        slens = np.frombuffer(buf[off:off + 4 * n], np.int32).tolist()
        off += 4 * n
        if fw:
            flat = np.frombuffer(buf[off:], np.int32).reshape(-1, fw)
            rows = list(map(tuple, flat.tolist()))
        else:
            rows = []
        roff = 0
        for k, m in zip(lcps, slens):
            entry = prev[:k] + tuple(rows[roff:roff + m])
            roff += m
            out.append(entry)
            prev = entry
    return out


def _packed_explored(dpor) -> Dict[str, Any]:
    """Incremental pack of the explored log: the log is append-only
    (rolled back only to an earlier prefix of the same history by the
    window snapshot/restore machinery), so the pack cache keeps the
    compressed delta frames of everything already packed and each
    snapshot encodes only the suffix admitted since — O(delta) encode
    per checkpoint, not O(explored). The cache self-validates with a
    prefix-length + last-entry (content digest) check and rebuilds from
    scratch when a rollback invalidated it. The log is columnar
    (device/explored_log.py); the frames' tuples are materialized here,
    a slab at a time, and not kept."""
    log = dpor._explored_log
    cache = dpor._persist_pack_cache
    if (
        cache is None
        or cache["count"] > len(log)
        or (cache["count"] > 0 and log.key(cache["count"] - 1) != cache["last"])
    ):
        cache = {"count": 0, "w": 0, "frames": [], "last": None}
    count = cache["count"]
    if count < len(log):
        prev = log[count - 1] if count else ()
        frame, w, _last = _encode_explored_frame(
            log.stream(range(count, len(log))), prev, cache["w"]
        )
        cache["frames"] = list(cache["frames"]) + [_b64(frame)]
        cache["w"] = w
        cache["count"] = len(log)
        cache["last"] = log.key(len(log) - 1)
    dpor._persist_pack_cache = cache
    return {
        "n": cache["count"], "w": cache["w"],
        "frames": list(cache["frames"]),
    }


def device_dpor_payload(dpor) -> Dict[str, Any]:
    """JSON-able snapshot of everything a DeviceDPOR round mutates (the
    durable twin of ``_dpor_search_state`` in device/dpor_sweep.py —
    keep the two field lists in sync). Bulk sections — the explored log,
    guides, sleep rows — ride packed int32 blobs; the frontier (and
    every per-prescription side table key) serializes as INDICES into
    the explored log, since every frontier entry was admitted."""
    import numpy as np

    explored = _packed_explored(dpor)  # also refreshes the pack cache
    # The side tables are keyed by tuples: their log index by digest.
    log_index = dpor._explored_log.index_of
    tuner = None
    if dpor.tuner is not None:
        tuner = {
            "rounds": dpor.tuner.rounds,
            "round_batch": dpor.tuner.round_batch,
            "max_distance": dpor.tuner.max_distance,
        }
    sleep = None
    if dpor.sleep is not None:
        class_keys = sorted(dpor.sleep.classes)
        masks: List[int] = []
        plens: List[int] = []
        class_dmasks: List[int] = []
        class_guides: List[list] = []
        for k in class_keys:
            m = dpor.sleep.class_meta.get(k)
            if m is None:
                masks.append(-1)  # recompute lazily on restore
                plens.append(-1)
                class_dmasks.append(-1)
                class_guides.append([])
            else:
                masks.append(int(m[0]))
                plens.append(int(m[1]) if m[2] is not None else -1)
                class_dmasks.append(
                    int(m[3]) if len(m) > 3 and m[2] is not None else -1
                )
                class_guides.append(
                    [list(r) for r in m[2]] if m[2] is not None else []
                )
        sleep = {
            "classes": _pack_rows(class_keys),
            "class_masks": masks,
            "class_plens": plens,
            "class_dmasks": class_dmasks,
            "class_guides": _pack_rows(class_guides),
            "node_flip_keys": [
                _b64(k) for k in sorted(dpor.sleep._node_flips)
            ],
            "node_flip_rows": _pack_rows(
                [dpor.sleep._node_flips[k]
                 for k in sorted(dpor.sleep._node_flips)]
            ),
            "pruned_total": dict(dpor.sleep.pruned_total),
        }
    sleep_keys = sorted(dpor._sleep_rows, key=log_index)
    guide_keys = sorted(dpor._guides, key=log_index)
    class_of_keys = sorted(dpor._class_of, key=log_index)
    witnesses = []
    for code in sorted(dpor.violation_witnesses):
        w = dpor.violation_witnesses[code]
        ck = w.get("class")
        witnesses.append({
            "code": int(code),
            "sha": str(w.get("sha", "")),
            "class": None if ck is None else [list(r) for r in ck],
            "trace": (
                pack_array(np.asarray(w["trace"]))
                if w.get("trace") is not None else None
            ),
        })
    return {
        "workload": device_dpor_workload(dpor),
        "explored": explored,
        "explored_digests": _pack_digests(dpor._explored_digests),
        "frontier": _pack_ints(dpor.frontier.indices()),
        "original": (
            None if dpor.original is None
            else [list(r) for r in dpor.original]
        ),
        "max_distance": dpor.max_distance,
        "interleavings": dpor.interleavings,
        "round_index": dpor.round_index,
        "round_batch": dpor.round_batch,
        "async_stats": dict(dpor.async_stats),
        "tuner": tuner,
        "host_seconds": dpor.host_seconds,
        "device_seconds": dpor.device_seconds,
        "sleep_rows_keys": _pack_ints(
            log_index(p) for p in sleep_keys
        ),
        "sleep_rows_vals": _pack_rows(
            [dpor._sleep_rows[p] for p in sleep_keys]
        ),
        "suppressed_digests": _pack_digests(dpor._suppressed_digests),
        "violation_codes": sorted(dpor.violation_codes),
        "guides_keys": _pack_ints(log_index(p) for p in guide_keys),
        "guides_vals": _pack_rows(
            [np.asarray(dpor._guides[p]).tolist() for p in guide_keys]
        ),
        "class_of_keys": _pack_ints(
            log_index(p) for p in class_of_keys
        ),
        "class_of_vals": _pack_rows(
            [[list(r) for r in dpor._class_of[p]] for p in class_of_keys]
        ),
        "violation_witnesses": witnesses,
        "sleep_state": sleep,
        "batch_size_hint": (
            None if dpor._batch_size_hint is None
            else list(dpor._batch_size_hint)
        ),
    }


def restore_device_dpor(dpor, payload: Dict[str, Any]) -> None:
    """Inverse of ``device_dpor_payload``: overwrite the instance's
    search state so the next round continues bit-identically. Raises
    ``CheckpointMismatch`` when the payload's workload shape differs."""
    import numpy as np

    want = device_dpor_workload(dpor)
    got = dict(payload.get("workload", {}))
    # Checkpoints written before PR 43 name the host path that wrote
    # them. The per-lane 'legacy' one deduped on tuples and never kept
    # the digest set the search now decides membership by, so its
    # search cannot continue; 'vectorized' is the path there is.
    host_path = got.pop("host_path", "vectorized")
    if host_path != "vectorized":
        raise CheckpointMismatch(
            f"checkpoint workload key 'host_path' is {host_path!r}: that "
            "host path is gone and its checkpoints carry no digest set "
            "to continue from"
        )
    if got != want:
        raise CheckpointMismatch(
            f"checkpoint workload {got!r} != this explorer's {want!r}"
        )
    log = _decode_explored_frames(payload["explored"]["frames"])
    # The log and its digest set, rebuilt in columns from the tuples
    # (the digests are recomputed: the payload's set is theirs).
    dpor.load_tuples(log)
    # Seed the pack cache from the loaded frames so the first checkpoint
    # after a resume encodes only what the resumed run adds.
    dpor._persist_pack_cache = {
        "count": len(log),
        "w": int(payload["explored"]["w"]),
        "frames": list(payload["explored"]["frames"]),
        "last": dpor._explored_log.key(len(log) - 1) if log else None,
    }
    dpor.frontier = _unpack_ints(payload["frontier"])
    dpor.original = (
        None if payload["original"] is None else _tt(payload["original"])
    )
    dpor.max_distance = payload["max_distance"]
    dpor.interleavings = payload["interleavings"]
    # Journal continuity (obs/journal.py): the resumed explorer's next
    # round continues the dead run's numbering, so the round journal
    # stays generation-contiguous (older payloads default to 0).
    dpor.round_index = int(payload.get("round_index", 0))
    dpor.round_batch = payload["round_batch"]
    dpor.async_stats = dict(payload["async_stats"])
    dpor.host_seconds = payload["host_seconds"]
    dpor.device_seconds = payload["device_seconds"]
    dpor._sleep_rows = {
        log[i]: rows
        for i, rows in zip(
            _unpack_ints(payload["sleep_rows_keys"]),
            _unpack_rows(payload["sleep_rows_vals"]),
        )
    }
    dpor._suppressed_digests = _unpack_digests(
        payload["suppressed_digests"]
    )
    if getattr(dpor, "_sharder", None) is not None:
        # Checkpoints carry the digest sets FLAT (shard-count-free), so
        # a sharded instance re-partitions them by digest range here —
        # which is also the whole N→M re-shard story: restore an
        # N-shard run's checkpoint into an M-shard explorer and the
        # ranges re-cut themselves (tests/test_host_shards.py).
        from ..fleet.shard import DigestShards

        dpor._explored_digests = DigestShards(
            dpor._host_shards, dpor._explored_digests
        )
        dpor._suppressed_digests = DigestShards(
            dpor._host_shards, dpor._suppressed_digests
        )
    dpor.violation_codes = set(payload["violation_codes"])
    dpor._guides = {
        log[i]: np.asarray(rows, np.int32)
        for i, rows in zip(
            _unpack_ints(payload["guides_keys"]),
            _unpack_rows(payload["guides_vals"]),
        )
    }
    dpor._batch_size_hint = (
        None if payload.get("batch_size_hint") is None
        else tuple(payload["batch_size_hint"])
    )
    dpor._class_of = {}
    if "class_of_keys" in payload:
        dpor._class_of = {
            log[i]: tuple(tuple(r) for r in rows)
            for i, rows in zip(
                _unpack_ints(payload["class_of_keys"]),
                _unpack_rows(payload["class_of_vals"]),
            )
        }
    dpor.violation_witnesses = {}
    for w in payload.get("violation_witnesses", ()):
        ck = w.get("class")
        dpor.violation_witnesses[int(w["code"])] = {
            "sha": str(w.get("sha", "")),
            "class": (
                None if ck is None else tuple(tuple(r) for r in ck)
            ),
            "trace": (
                unpack_array(w["trace"])
                if w.get("trace") is not None else None
            ),
        }
    if payload["tuner"] is not None and dpor.tuner is not None:
        dpor.tuner.rounds = payload["tuner"]["rounds"]
        dpor.tuner.round_batch = payload["tuner"]["round_batch"]
        dpor.tuner.max_distance = payload["tuner"]["max_distance"]
    if payload["sleep_state"] is not None and dpor.sleep is not None:
        sleep = payload["sleep_state"]
        class_keys = _unpack_rows(sleep["classes"])
        dpor.sleep.classes = set(class_keys)
        dpor.sleep.class_meta = {}
        if "class_masks" in sleep:
            sorted_keys = sorted(dpor.sleep.classes)
            masks = sleep["class_masks"]
            plens = sleep.get("class_plens", [-1] * len(sorted_keys))
            dmasks = sleep.get("class_dmasks", [-1] * len(sorted_keys))
            guides = _unpack_rows(sleep["class_guides"])
            for i, k in enumerate(sorted_keys):
                mask = int(masks[i])
                if mask < 0:
                    # No meta was recorded for this class (e.g. merged
                    # from a worker ledger): leave it absent so a
                    # re-checkpoint round-trips bit-identically.
                    continue
                plen = int(plens[i])
                guide = (
                    tuple(tuple(int(x) for x in r) for r in guides[i])
                    if plen >= 0 and i < len(guides) else None
                )
                dpor.sleep.class_meta[k] = (
                    mask,
                    plen if guide is not None else -1,
                    guide,
                    int(dmasks[i])
                    if guide is not None and i < len(dmasks) else -1,
                )
        dpor.sleep._node_flips = {
            _unb64(k): [tuple(r) for r in rows]
            for k, rows in zip(
                sleep["node_flip_keys"],
                _unpack_rows(sleep["node_flip_rows"]),
            )
        }
        dpor.sleep.pruned_total = dict(sleep["pruned_total"])


# ---------------------------------------------------------------------------
# Host DPORScheduler payload
# ---------------------------------------------------------------------------

def _prio_to_json(p: float):
    return "inf" if p == float("inf") else p


def _prio_from_json(p):
    return float("inf") if p == "inf" else p


def host_dpor_payload(sched) -> Dict[str, Any]:
    """JSON-able snapshot of a host DPORScheduler's resumable search
    state: dep-graph records (fingerprints via the serialization codec),
    the backtrack heap, explored set, and sleep ledgers."""
    from ..serialization import _fp_to_json

    records = []
    for rec in sched.tracker.to_records():
        rec = dict(rec)
        rec["fp"] = _fp_to_json(rec["fp"])
        records.append(rec)
    return {
        "tracker": records,
        "backtracks": [
            [_prio_to_json(prio), cnt, list(prefix)]
            for prio, cnt, prefix in sched._backtracks
        ],
        "explored": sorted(list(p) for p in sched._explored),
        "push_counter": sched._push_counter,
        "interleavings_explored": sched.interleavings_explored,
        "original_trace_ids": sched.original_trace_ids,
        "max_distance": sched.max_distance,
        "sleep_pruned": sched.sleep_pruned,
        "sleep": sorted(
            [list(prefix), sorted(ids)]
            for prefix, ids in sched._sleep.items()
        ),
        "node_children": sorted(
            [list(prefix), list(ids)]
            for prefix, ids in sched._node_children.items()
        ),
    }


def restore_host_dpor(sched, payload: Dict[str, Any]) -> None:
    """Inverse of ``host_dpor_payload``. The scheduler must be freshly
    constructed with the same config/ordering arguments."""
    import heapq

    from ..schedulers.dep_tracker import DepTracker
    from ..serialization import _fp_from_json

    records = []
    for rec in payload["tracker"]:
        rec = dict(rec)
        rec["fp"] = _fp_from_json(rec["fp"])
        records.append(rec)
    sched.tracker = DepTracker.from_records(
        records, sched.config.fingerprinter
    )
    backtracks = [
        (_prio_from_json(prio), cnt, tuple(prefix))
        for prio, cnt, prefix in payload["backtracks"]
    ]
    heapq.heapify(backtracks)
    sched._backtracks = backtracks
    sched._explored = {tuple(p) for p in payload["explored"]}
    sched._push_counter = payload["push_counter"]
    sched.interleavings_explored = payload["interleavings_explored"]
    sched.original_trace_ids = payload["original_trace_ids"]
    sched.max_distance = payload["max_distance"]
    sched.sleep_pruned = payload["sleep_pruned"]
    sched._sleep = {
        tuple(prefix): set(ids) for prefix, ids in payload["sleep"]
    }
    sched._node_children = {
        tuple(prefix): list(ids)
        for prefix, ids in payload["node_children"]
    }
    if sched._arvind_pending and sched.original_trace_ids is not None:
        from ..schedulers.dpor import ArvindDistanceOrdering

        sched.ordering = ArvindDistanceOrdering(sched.original_trace_ids)
        sched._arvind_pending = False


# ---------------------------------------------------------------------------
# ExplorationController / fuzzer payload
# ---------------------------------------------------------------------------

def controller_payload(controller) -> Dict[str, Any]:
    """Delegates to ExplorationController.checkpoint_state (the corpus
    fingerprint set + weight-tuner coordinates + live fuzzer weights)."""
    return controller.checkpoint_state()


def restore_controller(controller, payload: Dict[str, Any]) -> None:
    controller.restore_state(payload)
