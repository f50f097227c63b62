"""Crash-safe checkpoint I/O: atomic writes, integrity manifests, rotation.

The pre-existing ``tmp.replace(path)`` save was atomic against a crash of
*this* process but still trusted the file's bytes: a torn write below the
rename (power loss, full disk returning short writes, a copy truncated by a
dying NFS client) produced a ``last.ckpt`` that parses partway and then
kills the restarted run — the worst failure mode, because it defeats the
resume machinery exactly when it is needed.

Three mechanisms close that hole:

- ``atomic_write_bytes`` — tmp file + ``flush`` + ``fsync`` + ``os.replace``
  + directory fsync, so the rename itself is durable, not just ordered;
- a sidecar **manifest** (``<name>.manifest.json``) carrying the payload's
  SHA-256, byte count, and train-state metadata (step, epoch, mesh shape);
  written *after* the payload so a crash between the two leaves a stale
  manifest that fails verification (never a fresh manifest blessing torn
  bytes);
- **rotation**: before a new ``last.ckpt`` lands, the previous verified one
  is renamed to ``prev-last.ckpt`` — restore falls back to it when the
  newest file fails its manifest check.

Checkpoints written before this module existed have no manifest;
``verify_checkpoint`` accepts them (legacy mode) so old run dirs keep
resuming.
"""

from __future__ import annotations

import hashlib
import json
import os
import queue
import threading
from pathlib import Path

MANIFEST_SUFFIX = ".manifest.json"
PREV_PREFIX = "prev-"

# read/hash pipeline granularity: large enough that hashlib releases the
# GIL for real work per chunk, small enough that two in-flight chunks are
# noise next to a multi-GB state
HASH_CHUNK_BYTES = 8 << 20
# below this size the pipeline is pure overhead: a page-cached read is a
# memcpy the hash cannot hide behind, and the thread/chunking tax was
# MEASURED at ~2x a plain read-then-hash on the CI host — so small states
# keep the exact pre-existing serial pass, and the pipeline engages only
# where it was designed to win: multi-GB states whose storage read is the
# long pole
PIPELINE_MIN_BYTES = 256 << 20


def read_and_hash(
    path: str | Path,
    chunk_bytes: int = HASH_CHUNK_BYTES,
    pipeline_min_bytes: int = PIPELINE_MIN_BYTES,
) -> tuple[bytes | bytearray, str]:
    """One-pass read + SHA-256 of a checkpoint payload, pipelined when the
    payload is large enough for overlap to pay.

    Verify-on-restore reads the file once and serves both the checksum and
    the restore from the same buffer.  Below ``pipeline_min_bytes`` that is
    a plain read-then-hash (fastest for warm/small files).  Above it, a
    reader thread ``readinto``s chunk *i+1* of a preallocated buffer while
    the main thread hashes chunk *i* (both sides release the GIL at these
    chunk sizes, so the overlap is real and assembly is zero-copy): for
    multi-GB states on real storage — where the read, not the hash, is the
    long pole — the wall-clock approaches ``max(read, hash)`` instead of
    their sum.

    Returns ``(data, hexdigest)`` — ``data`` is bytes-like (``bytes`` on the
    small path, the pipeline's ``bytearray`` on the large one: returning the
    buffer itself keeps peak host memory at ONE state's worth instead of
    doubling a multi-GB restore with a defensive copy).  Callers treat it as
    read-only; every consumer (msgpack restore, ``len``, ``sha256``) takes
    any buffer-protocol object.  Reader errors (including the file shrinking
    mid-read) re-raise here.
    """
    path = Path(path)
    size = path.stat().st_size
    if size < pipeline_min_bytes:
        data = path.read_bytes()
        return data, hashlib.sha256(data).hexdigest()
    buf = bytearray(size)
    view = memoryview(buf)
    q: queue.Queue = queue.Queue(maxsize=2)
    stop = threading.Event()

    def read() -> None:
        try:
            with open(path, "rb") as f:
                offset = 0
                while not stop.is_set() and offset < size:
                    want = min(chunk_bytes, size - offset)
                    got = f.readinto(view[offset : offset + want])
                    if not got:
                        raise OSError(
                            f"{path} truncated while reading: expected "
                            f"{size} bytes, got {offset}"
                        )
                    q.put((offset, got))
                    offset += got
                q.put(None)
        except BaseException as e:  # surfaced at the consumer
            q.put(e)

    thread = threading.Thread(target=read, name="dtc-ckpt-read", daemon=True)
    thread.start()
    digest = hashlib.sha256()
    try:
        while True:
            try:
                item = q.get(timeout=5.0)
            except queue.Empty:
                # same dead-producer guard as PrefetchLoader: a reader that
                # died without enqueueing (not even its exception) must not
                # hang restore forever on a bare get
                if not thread.is_alive():
                    raise OSError(
                        f"{path}: checkpoint reader thread died without "
                        "delivering a result"
                    )
                continue
            if item is None:
                break
            if isinstance(item, BaseException):
                raise item
            offset, got = item
            digest.update(view[offset : offset + got])
    finally:
        stop.set()
        try:
            while True:
                q.get_nowait()
        except queue.Empty:
            pass
        thread.join(timeout=10.0)
    return buf, digest.hexdigest()


def hash_file(path: str | Path, chunk_bytes: int = HASH_CHUNK_BYTES) -> str:
    """Streaming SHA-256 of a file in O(chunk_bytes) host memory — the
    digest-only verify path must not allocate a whole multi-GB state just to
    throw the bytes away."""
    digest = hashlib.sha256()
    buf = bytearray(chunk_bytes)
    view = memoryview(buf)
    with open(path, "rb") as f:
        while True:
            got = f.readinto(view)
            if not got:
                break
            digest.update(view[:got])
    return digest.hexdigest()


def manifest_path(path: str | Path) -> Path:
    path = Path(path)
    return path.with_name(path.name + MANIFEST_SUFFIX)


def previous_path(path: str | Path) -> Path:
    """The rotation target for ``path`` (``last.ckpt`` → ``prev-last.ckpt``)."""
    path = Path(path)
    return path.with_name(PREV_PREFIX + path.name)


def _fsync_dir(directory: Path) -> None:
    """Make a rename in ``directory`` durable (POSIX: the rename is only on
    disk once the directory inode is)."""
    try:
        fd = os.open(directory, os.O_RDONLY)
    except OSError:  # e.g. O_RDONLY on a dir unsupported (some platforms)
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def atomic_write_bytes(path: str | Path, data: bytes, durable: bool = True) -> Path:
    """Write ``data`` to ``path`` via tmp+fsync+rename: readers never observe
    a partial file, and after return the content survives power loss."""
    path = Path(path)
    tmp = path.with_name(path.name + f".tmp.{os.getpid()}")
    with open(tmp, "wb") as f:
        f.write(data)
        if durable:
            f.flush()
            os.fsync(f.fileno())
    os.replace(tmp, path)
    if durable:
        _fsync_dir(path.parent)
    return path


# the most one write (and one hash update) takes at once: what a paced
# writer has under way when it is asked to stop
_PIECE_BYTES = 32 << 20


def atomic_write_chunks(
    path: str | Path, chunks, durable: bool = True, pace=None
) -> tuple[Path, str, int]:
    """``atomic_write_bytes`` for a payload that arrives in pieces (bytes
    or C-contiguous byte buffers), hashed while it is written: returns
    ``(path, sha256 hex, size)``.  A multi-gigabyte train state is written
    from its arrays' own memory, never joined into one ``bytes``; writing
    and hashing a large buffer both release the GIL, so a writer thread
    here does not hold up the training thread.  ``pace()``, if given, is
    called before every write of at most 32 MiB and may block: the
    checkpoint writer stops there while the trainer's thread paces the
    chip (``AsyncCheckpointer.pace``)."""
    path = Path(path)
    tmp = path.with_name(path.name + f".tmp.{os.getpid()}")
    digest, size = hashlib.sha256(), 0
    with open(tmp, "wb") as f:
        for chunk in chunks:
            view = memoryview(chunk).cast("B")
            for at in range(0, len(view), _PIECE_BYTES):
                piece = view[at:at + _PIECE_BYTES]
                if pace is not None:
                    pace()
                f.write(piece)
                digest.update(piece)
            size += len(view)
        if durable:
            f.flush()
            os.fsync(f.fileno())
    os.replace(tmp, path)
    if durable:
        _fsync_dir(path.parent)
    return path, digest.hexdigest(), size


def write_manifest(
    path: str | Path, data: bytes | None = None, meta: dict | None = None,
    *, digest: str | None = None, size: int | None = None,
) -> Path:
    """Write the sidecar integrity manifest for a payload already at
    ``path`` whose bytes are ``data`` (or whose ``digest`` and ``size``
    ``atomic_write_chunks`` returned).  Call AFTER the payload write: the
    crash window then holds a stale manifest (checksum mismatch → fallback),
    never a fresh manifest over torn bytes."""
    record = {
        "sha256": digest or hashlib.sha256(data).hexdigest(),
        "bytes": size if data is None else len(data),
        **(meta or {}),
    }
    return atomic_write_bytes(
        manifest_path(path), json.dumps(record, indent=1).encode()
    )


def read_manifest(path: str | Path) -> dict | None:
    """The manifest dict for checkpoint ``path``, or None (missing/corrupt)."""
    mpath = manifest_path(path)
    try:
        return json.loads(mpath.read_bytes())
    except (OSError, ValueError):
        return None


def verify_checkpoint(
    path: str | Path,
    deep: bool = True,
    data: bytes | None = None,
    digest: str | None = None,
) -> tuple[bool, str]:
    """``(ok, reason)`` for the payload at ``path`` against its manifest.

    ``deep=False`` skips the checksum (size-only) — the cheap pre-rotation
    check, so each epoch's save does not re-hash the previous multi-GB file.
    ``data`` lets a caller that has already read the payload (to restore
    it) verify that buffer instead of paying a second full-file read;
    ``digest`` additionally skips re-hashing when the caller got both from
    ``read_and_hash`` (the hash was computed while the read was in flight —
    the whole verify then costs ~zero extra over the restore read).  A
    checkpoint without a manifest is accepted as legacy (pre-manifest run
    dirs must keep resuming); its parseability is the loader's problem.
    """
    path = Path(path)
    if not path.exists():
        return False, "missing"
    manifest = read_manifest(path)
    if manifest is None:
        # Absent manifest = legacy checkpoint, accepted.  A manifest that
        # EXISTS but does not parse is corruption in the same event that
        # may have torn the payload — rejecting it sends restore to the
        # verified prev- fallback instead of trusting unverifiable bytes
        # (and keeps rotate_previous from evicting the good prev copy).
        if manifest_path(path).exists():
            return False, "manifest present but unreadable (corrupted)"
        return True, "no manifest (legacy checkpoint, accepted unverified)"
    size = len(data) if data is not None else path.stat().st_size
    if size != manifest.get("bytes"):
        return False, f"size mismatch: {size} on disk vs {manifest.get('bytes')} in manifest"
    if deep:
        if data is not None and digest is not None:
            found = digest
        elif data is not None:
            found = hashlib.sha256(data).hexdigest()
        else:
            found = hash_file(path)
        if found != manifest.get("sha256"):
            return False, "checksum mismatch (torn or corrupted write)"
    return True, "verified"


def rotate_previous(path: str | Path) -> Path | None:
    """Rename an existing (size-valid) ``path`` + manifest to the ``prev-``
    slot, making room for a new write while keeping one good fallback.

    A size-invalid current file is NOT rotated — it would evict a good
    ``prev-`` checkpoint in favor of known-torn bytes.  Returns the rotated
    path, or None if nothing was rotated.
    """
    path = Path(path)
    if not path.exists():
        return None
    ok, _ = verify_checkpoint(path, deep=False)
    if not ok:
        return None
    prev = previous_path(path)
    os.replace(path, prev)
    mpath = manifest_path(path)
    prev_manifest = manifest_path(prev)
    if mpath.exists():
        os.replace(mpath, prev_manifest)
    else:  # legacy current had no manifest: drop any stale prev manifest
        prev_manifest.unlink(missing_ok=True)
    return prev


# --------------------------------------------- quarantine persistence

QUARANTINE_SIDECAR_PREFIX = "quarantine-p"


def quarantine_sidecar_path(directory: str | Path, process_index: int) -> Path:
    """Per-rank quarantine sidecar next to the checkpoints: the resume
    manifest is written by process 0 only, so under multi-host it carries
    only rank 0's corrupt-shard set — every rank persists its OWN set
    here, and a relaunch unions them all back."""
    return Path(directory) / f"{QUARANTINE_SIDECAR_PREFIX}{int(process_index)}.json"


def write_quarantine_sidecar(
    directory: str | Path, process_index: int, example_ids,
) -> Path | None:
    """Persist one rank's quarantined example ids (rename-atomic; no
    fsync — the set is advisory next to the durable checkpoint).  Empty
    sets write nothing; failures return None (quarantine persistence must
    never kill the rollback that produced it)."""
    ids = sorted(int(i) for i in example_ids or ())
    if not ids:
        return None
    path = quarantine_sidecar_path(directory, process_index)
    try:
        atomic_write_bytes(path, json.dumps(ids).encode(), durable=False)
    except OSError:
        return None
    return path


def _int_ids(seq) -> set[int]:
    """Coerce advisory id lists leniently: a non-integer entry (schema
    drift, a hand edit) is dropped, never raised — the quarantine files
    must not be able to block a resume."""
    out: set[int] = set()
    for i in seq or ():
        try:
            out.add(int(i))
        except (TypeError, ValueError):
            continue
    return out


def union_quarantine(directory: str | Path, base=None) -> list[int]:
    """The fleet-wide quarantine set at resume time: the manifest's list
    (rank 0's, ``base``) unioned with every ``quarantine-p*.json`` sidecar
    in the checkpoint's directory.  Unreadable sidecars — and non-integer
    entries inside readable ones — are skipped: a torn or drifted
    advisory file must not block a resume."""
    merged = _int_ids(base)
    try:
        sidecars = sorted(
            Path(directory).glob(f"{QUARANTINE_SIDECAR_PREFIX}*.json")
        )
    except OSError:
        sidecars = []
    for path in sidecars:
        try:
            ids = json.loads(path.read_text())
        except (OSError, ValueError):
            continue
        if isinstance(ids, list):
            merged.update(_int_ids(ids))
    return sorted(merged)
