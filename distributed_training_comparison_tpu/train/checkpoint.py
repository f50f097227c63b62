"""Checkpointing: versioned run dirs, best-only policy, full resume state.

Parity: reference ``save_checkpoint`` — scan ``version-{n}`` dirs for the
first free slot (``src/single/trainer.py:52-59``), on val-top1 improvement
delete all old ``*.pt`` then save the model state as
``best_model_epoch_{e}_acc_{a}.pt`` (``:96-107``, ``:115-117``), rank-0-only
under ddp (``src/ddp/trainer.py:131-132``).  The reference saves **only**
model weights — no optimizer/scheduler/step — so a killed run cannot resume
(SURVEY.md §5).  Here ``last.ckpt`` carries the full train state (params, BN
stats, optimizer state, step, epoch, best-acc), making mid-run resume a
first-class capability.

Format: flax msgpack serialization of host-fetched pytrees — a single
portable file, no framework-pickle coupling (torch.load arbitrary-code
pickle is the reference's load path, ``src/single/main.py:25``).
"""

from __future__ import annotations

import logging
import re
import struct
from pathlib import Path
from typing import Any, Callable

import numpy as np
from flax import serialization

from ..parallel.layouts import tree_from_canonical, tree_to_canonical
from ..parallel.sharding import fetch_to_host
from ..resilience.ckpt_io import (
    atomic_write_bytes,
    atomic_write_chunks,
    previous_path,
    read_and_hash,
    read_manifest,
    rotate_previous,
    verify_checkpoint,
    write_manifest,
)
from .state import TrainState

_log = logging.getLogger("dtc_tpu")

BEST_PREFIX = "best_model_"
LAST_NAME = "last.ckpt"

# Checkpoint payload format.  3: the ViT attention input projections are
# three separate q_proj/k_proj/v_proj Denses (models/vit.py).  Formats 1-2
# used one packed 3*dim qkv Dense (format 1 q/k/v-major, format 2
# head-major); those checkpoints are structurally and semantically
# incompatible with the current trunk.
CKPT_FMT = 3


# arrays from this size up are written from their own memory (below it a
# leaf goes through flax's packer; its ext and bin headers are the 32-bit
# forms only above 64 KiB, which the streamed form writes by hand)
_STREAM_MIN_BYTES = 1 << 20
_EXT_NDARRAY = 1  # flax.serialization._MsgpackExtType.ndarray


def msgpack_chunks(tree):
    """The bytes ``flax.serialization.msgpack_serialize(tree)`` gives, as an
    iterator of pieces: headers as small ``bytes``, every large array as a
    view of its own memory.  ``msgpack_serialize`` copies each array four
    times into one ``bytes`` with the GIL held throughout — 5 s a gigabyte
    here, during which the training thread stands still (PERF.md, Findings,
    PR 27); these pieces go to ``atomic_write_chunks``, whose writes and
    hashing release it.  ``msgpack_restore`` reads the result back."""
    import msgpack

    if isinstance(tree, dict):
        yield msgpack.Packer().pack_map_header(len(tree))
        for key, value in sorted(tree.items()):  # flax's copy sorts keys
            yield msgpack.packb(key)
            yield from msgpack_chunks(value)
        return
    big = (
        isinstance(tree, np.ndarray)
        and _STREAM_MIN_BYTES <= tree.nbytes < serialization.MAX_CHUNK_SIZE
        and not (tree.dtype.hasobject or tree.dtype.isalignedstruct)
    )
    if not big:  # scalars, strings, small arrays, arrays flax would split
        yield serialization.msgpack_serialize(tree)
        return
    # ExtType(ndarray, packb((shape, dtype name, bytes), use_bin_type=True))
    head = (
        msgpack.Packer().pack_array_header(3)
        + msgpack.packb(list(tree.shape)) + msgpack.packb(tree.dtype.name)
        + b"\xc6" + struct.pack(">I", tree.nbytes)
    )
    yield (
        b"\xc9" + struct.pack(">I", len(head) + tree.nbytes)
        + struct.pack("b", _EXT_NDARRAY) + head
    )
    yield memoryview(np.ascontiguousarray(tree).reshape(-1).view(np.uint8))


def _check_ckpt_fmt(raw: dict, params, path) -> None:
    fmt = raw.get("fmt", 1)
    is_vit = isinstance(params, dict) and "q_proj" in params.get("blocks", {})
    if fmt < CKPT_FMT and is_vit:
        raise ValueError(
            f"{path} is a format-{fmt} ViT checkpoint from before the "
            "split q/k/v projections (current format "
            f"{CKPT_FMT}); its packed qkv kernel cannot be loaded into the "
            "current trunk. Retrain, or split the packed qkv columns into "
            "q_proj/k_proj/v_proj and re-save."
        )


def find_version_dir(ckpt_root: str | Path, create: bool = True) -> Path:
    """First nonexistent ``version-{n}`` under ``ckpt_root`` (reference
    ``src/single/trainer.py:52-59``).

    Claiming is race-safe: the scan-then-``mkdir(exist_ok=True)`` original
    had a TOCTOU hole — two processes scanning concurrently could both see
    ``version-3`` free and silently share it, interleaving their
    checkpoints.  Here the claim IS the ``mkdir(exist_ok=False)``: the
    filesystem arbitrates, the loser re-scans from the next index.
    """
    root = Path(ckpt_root)
    n = 0
    while True:
        d = root / f"version-{n}"
        if d.exists():
            n += 1
            continue
        if not create:
            return d
        try:
            d.mkdir(parents=True, exist_ok=False)
            return d
        except FileExistsError:  # lost the claim race; try the next slot
            n += 1


def agreed_version_dir(ckpt_root: str | Path) -> Path:
    """Multi-host version-dir choice: process 0 claims (race-safely), every
    other process follows its broadcast pick.

    Under ``jax.distributed`` each host scanning independently could claim
    different slots (local-FS ``ckpt_root``) or race each other (shared FS).
    This is a COLLECTIVE — every process must call it, in the same order
    relative to other collectives.  Non-zero processes do not ``mkdir``:
    on a shared FS the dir already exists, on local FS only process 0
    writes checkpoints anyway.
    """
    import jax

    if jax.process_count() == 1:
        return find_version_dir(ckpt_root)
    from jax.experimental import multihost_utils

    if jax.process_index() == 0:
        chosen = int(find_version_dir(ckpt_root).name.split("-")[-1])
    else:
        chosen = 0  # placeholder; broadcast overwrites with rank 0's claim
    chosen = int(multihost_utils.broadcast_one_to_all(np.asarray(chosen)))
    return Path(ckpt_root) / f"version-{chosen}"


def _state_dict(state: TrainState) -> dict[str, Any]:
    # comms_residual (the --grad-comms error-feedback carry) serializes
    # only when the state CARRIES one — the Trainer's _ckpt_view strips
    # it unless --ckpt-comms-residual asked for it, so the default
    # checkpoint stays bit-compatible across every --shard-optim/
    # --grad-comms combination and a resumed run restarts the residual at
    # zero (at most one step's quantization error).  load_resume_state
    # reconciles the key across saved-with/restoring-without boundaries
    # (the documented drop-and-warn path).  Sharded optimizer state needs
    # nothing here either — fetch_to_host gathers full host arrays
    # whatever the layout, and restore re-places them under the restoring
    # run's shardings (the reshard step).
    out = {
        "step": state.step,
        "params": state.params,
        "batch_stats": state.batch_stats,
        "opt_state": state.opt_state,
    }
    if state.comms_residual is not None:
        out["comms_residual"] = state.comms_residual
    return out


# Device→host reads below go through fetch_to_host: shard-safe for
# replicated multi-host leaves (local read), but cross-host-partitioned
# leaves require a symmetric collective — the Trainer pre-fetches those on
# every process before handing the (then host-numpy) state to the writer
# thread (see trainer.fit / parallel.needs_collective_fetch).


def save_checkpoint(
    version_dir: str | Path,
    state: TrainState,
    epoch: int,
    val_acc: float,
    state_layout=None,
    pace=None,
) -> Path:
    """Best-only save: drop previous best files, write the new one.
    ``pace`` goes to ``atomic_write_chunks``.

    File carries params + batch_stats (what inference needs); the resumable
    full state lives in ``last.ckpt``.  On disk the trunk stack is always
    CANONICAL (contiguous depth-major): ``state_layout`` describes the
    live state's resident layout so a chunk-resident interleaved run still
    writes the same bytes a contiguous run would — any future run (any
    schedule) restores it through its own layout seam.
    """
    version_dir = Path(version_dir)
    params_host = serialization.to_state_dict(fetch_to_host(state.params))
    if state_layout is not None:
        params_host = tree_to_canonical(params_host, state_layout)
    payload = {
        "fmt": CKPT_FMT,
        "params": params_host,
        "batch_stats": serialization.to_state_dict(fetch_to_host(state.batch_stats)),
        "epoch": epoch,
        "val_acc": float(val_acc),
    }
    path = version_dir / f"{BEST_PREFIX}epoch_{epoch}_acc_{val_acc:.4f}.ckpt"
    atomic_write_chunks(path, msgpack_chunks(payload), pace=pace)
    # drop superseded best files only AFTER the new one is durably in place
    # — a crash mid-save (fetch can take seconds) must never leave the
    # version dir with zero best checkpoints
    for old in version_dir.glob(f"{BEST_PREFIX}*.ckpt"):
        if old != path:
            old.unlink()
    return path


def load_checkpoint(path: str | Path, state: TrainState, state_layout=None) -> TrainState:
    """Restore params/batch_stats from a best checkpoint into ``state``.

    Checkpoints are canonical on disk; ``state_layout`` converts the
    restored trunk stack to the live state's resident layout so the
    returned state matches the installed schedule's shapes."""
    raw = serialization.msgpack_restore(Path(path).read_bytes())
    _check_ckpt_fmt(raw, state.params, path)
    params = serialization.from_state_dict(state.params, raw["params"])
    if state_layout is not None:
        params = tree_from_canonical(params, state_layout)
    batch_stats = serialization.from_state_dict(state.batch_stats, raw["batch_stats"])
    return state.replace(params=params, batch_stats=batch_stats)


def _version_dirs_newest_first(ckpt_root: str | Path) -> list[Path]:
    """``version-{n}`` dirs under ``ckpt_root``, numerically newest first —
    the one discovery rule --auto-resume and the serve engine share (so
    both always agree on which run is 'newest')."""
    dirs = [
        d
        for d in Path(ckpt_root).glob("version-*")
        if d.name.split("-")[-1].isdigit()
    ]
    return sorted(dirs, key=lambda d: -int(d.name.split("-")[-1]))


def find_latest_resume(ckpt_root: str | Path) -> Path | None:
    """The NEWEST version dir's ``last.ckpt``, or None.

    The --auto-resume discovery step: a relaunched job picks up exactly
    where the newest run stopped (every process scans the same shared
    checkpoint path, so multi-host relaunches agree).  Only the newest
    version is considered — if it crashed before its first save (or ran
    with --no-save-last), auto-resume starts fresh rather than silently
    resuming into an older, possibly completed run's directory."""
    dirs = _version_dirs_newest_first(ckpt_root)
    if not dirs:
        return None
    path = dirs[0] / LAST_NAME
    return path if path.exists() else None


def valid_resume_bytes_in(version_dir: str | Path) -> tuple[Path, bytes] | None:
    """THIS version dir's ``last.ckpt`` if its integrity manifest checks
    out, else the rotated ``prev-last.ckpt``, else None — with the verified
    payload bytes (one disk read serves verify + restore).

    Shared by --auto-resume discovery (newest dir) and the health
    watchdog's rollback (the CURRENT run's dir): both must only ever hand
    back a state whose bytes verified."""
    newest = Path(version_dir) / LAST_NAME
    for candidate in (newest, previous_path(newest)):
        if not candidate.exists():
            continue
        # one pipelined pass: the SHA-256 of chunk i is computed while
        # chunk i+1 is read — verify costs ~nothing over the restore read
        data, digest = read_and_hash(candidate)
        ok, reason = verify_checkpoint(candidate, data=data, digest=digest)
        if ok:
            if candidate != newest:
                _log.warning(
                    f"resume: {newest.name} failed verification; falling "
                    f"back to previous good checkpoint {candidate.name}"
                )
            return candidate, data
        _log.warning(f"resume: rejecting {candidate}: {reason}")
    return None


def find_valid_resume_bytes(ckpt_root: str | Path) -> tuple[Path, bytes] | None:
    """Verify-on-restore discovery: the newest version dir's ``last.ckpt``
    only if its integrity manifest checks out, else the rotated previous
    good checkpoint (``prev-last.ckpt``), else None — returned WITH the
    verified payload bytes, so restore reuses the buffer instead of paying
    a second full read of a possibly multi-GB state.

    This is the discovery rule --auto-resume uses once resilience is in
    play: a torn ``last.ckpt`` (crash mid-write on a non-atomic filesystem,
    a dying disk, an injected ``torn_write`` fault) must cost one epoch of
    progress, never the run."""
    dirs = _version_dirs_newest_first(ckpt_root)
    if not dirs:
        return None
    return valid_resume_bytes_in(dirs[0])


def find_valid_resume(ckpt_root: str | Path) -> Path | None:
    """Path-only form of ``find_valid_resume_bytes``."""
    hit = find_valid_resume_bytes(ckpt_root)
    return hit[0] if hit else None


def resume_progress_marker(ckpt_root: str | Path) -> tuple | None:
    """A cheap durable-progress marker for the newest resumable checkpoint:
    its path plus the manifest's checksum/step/epoch fields.  Manifest-only
    — a size (shallow) verification, NO payload read or hash — so the
    supervisor can probe it between attempts at ~KB cost even for multi-GB
    states (the child's --auto-resume still deep-verifies before actually
    restoring).  None when no size-valid checkpoint exists."""
    dirs = _version_dirs_newest_first(ckpt_root)
    if not dirs:
        return None
    newest = dirs[0] / LAST_NAME
    for candidate in (newest, previous_path(newest)):
        if not candidate.exists():
            continue
        ok, _ = verify_checkpoint(candidate, deep=False)
        if not ok:
            continue
        manifest = read_manifest(candidate) or {}
        return (
            str(candidate),
            manifest.get("sha256"),
            manifest.get("step"),
            manifest.get("epoch"),
            manifest.get("epoch_steps_done"),
        )
    return None


def _best_sort_key(path: Path) -> tuple[int, float]:
    """(epoch, acc) parsed from ``best_model_epoch_{e}_acc_{a}.ckpt``.

    Numeric, not lexicographic: ``epoch_9`` must lose to ``epoch_10`` even
    though it sorts after it as a string.  Unparseable names sort first so a
    well-formed file always wins over a stray one."""
    m = re.fullmatch(
        rf"{BEST_PREFIX}epoch_(\d+)_acc_([0-9.]+)\.ckpt", path.name
    )
    if not m:
        return (-1, -1.0)
    try:
        return (int(m.group(1)), float(m.group(2).rstrip(".")))
    except ValueError:  # e.g. acc "1.2.3" — regex-matched but not a float
        return (-1, -1.0)


def find_best_checkpoint(version_dir: str | Path, cleanup: bool = False) -> Path | None:
    """Glob the best file like the reference's test phase
    (``src/single/main.py:23-27``) — but pick by numeric epoch (highest-acc
    tiebreak), not string order.

    Two best files can coexist in the crash window of ``save_checkpoint``
    (new file written before old ones are unlinked); ``cleanup=True``
    restores the one-best invariant by dropping the stale losers.  It is
    opt-in: a lookup must not mutate the version dir by default —
    concurrent readers (multi-host processes, external monitors, a test
    phase against a live training dir) could race the unlinks (advisor
    r3).  The steady-state invariant holder is ``save_checkpoint``, which
    unlinks superseded bests after each durable write.  When cleanup does
    run, only files this module's own naming scheme accounts for are ever
    deleted — a user's stray ``best_model_backup.ckpt`` is not ours to
    unlink."""
    hits = sorted(Path(version_dir).glob(f"{BEST_PREFIX}*.ckpt"), key=_best_sort_key)
    if not hits:
        return None
    best = hits[-1]
    if cleanup:
        for stale in hits[:-1]:
            if _best_sort_key(stale) != (-1, -1.0):
                stale.unlink(missing_ok=True)
    return best


def load_eval_variables(path: str | Path, variables: dict) -> tuple[dict, dict]:
    """Restore ``{"params", "batch_stats"}`` from a checkpoint into a
    ``model.init``-shaped variables template — the inference-side loader
    (serve engine, eval tools): no ``TrainState``/optimizer needed.

    Accepts either payload format: a best checkpoint (params + stats at
    the top level) or a resumable ``last.ckpt`` (full state nested under
    ``"state"`` — the optimizer leaves are simply ignored).  Returns the
    restored variables and a metadata dict (epoch + the accuracy field
    the file carries).
    """
    raw = serialization.msgpack_restore(Path(path).read_bytes())
    _check_ckpt_fmt(raw, variables.get("params", {}), path)
    if "state" in raw:  # last.ckpt layout
        src = raw["state"]
        acc = float(raw.get("best_acc", 0.0))
    else:  # best_model_* layout
        src = raw
        acc = float(raw.get("val_acc", 0.0))
    restored = {
        "params": serialization.from_state_dict(
            variables["params"], src["params"]
        ),
        "batch_stats": serialization.from_state_dict(
            variables.get("batch_stats", {}), src["batch_stats"]
        ),
    }
    return restored, {"epoch": int(raw.get("epoch", -1)), "acc": acc}


def find_serving_checkpoint(ckpt_root: str | Path) -> Path | None:
    """Newest version dir's best checkpoint (falling back to its
    ``last.ckpt``) — the serve engine's default discovery, scanning the
    same ``version-{n}`` layout training writes."""
    for d in _version_dirs_newest_first(ckpt_root):
        best = find_best_checkpoint(d)
        if best is not None:
            return best
        last = d / LAST_NAME
        if last.exists():
            return last
    return None


def save_resume_state(
    version_dir: str | Path,
    state: TrainState,
    epoch: int,
    best_acc: float,
    fault_hook: Callable[[str, Path], None] | None = None,
    meta: dict | None = None,
    state_layout=None,
    pace=None,
) -> Path:
    """Write the fully-resumable ``last.ckpt`` (capability the reference
    lacks), crash-safely:

    1. the existing (size-valid) ``last.ckpt`` rotates to ``prev-last.ckpt``
       — the fallback verify-on-restore reaches for;
    2. the payload lands via tmp+fsync+rename (never a torn visible file
       from a crash of THIS process);
    3. a sidecar manifest (payload SHA-256 + step/epoch/mesh metadata) is
       written after the payload, so external corruption — or a crash
       between the two writes — fails verification instead of poisoning the
       next restart.

    ``fault_hook(stage, path)`` is the fault-injection seam
    (``FaultPlan.ckpt_hook``): ``"pre"`` may raise (write failure),
    ``"post"`` may corrupt the landed file (torn write).  ``meta`` merges
    into the manifest (the Trainer records the saving mesh topology for
    elastic-restore accounting).

    On disk the trunk stack is CANONICAL whatever ``state_layout`` the
    live state is resident in (the chunk view is a byte-preserving
    reshape, so this costs a numpy view); the manifest records the
    saving run's layout tag under ``state_layout`` so
    ``elastic.validate_reshard`` can report cross-layout restores.  The
    comms error-feedback residual is schedule-laid wire format, never
    canonicalized.  ``pace`` goes to ``atomic_write_chunks``."""
    host_state = serialization.to_state_dict(fetch_to_host(_state_dict(state)))
    if state_layout is not None:
        host_state = tree_to_canonical(host_state, state_layout)
    payload = {
        "fmt": CKPT_FMT,
        "state": host_state,
        "epoch": epoch,
        "best_acc": float(best_acc),
    }
    path = Path(version_dir) / LAST_NAME
    if fault_hook is not None:
        fault_hook("pre", path)
    rotate_previous(path)
    _, digest, size = atomic_write_chunks(
        path, msgpack_chunks(payload), pace=pace
    )
    write_manifest(
        path,
        digest=digest,
        size=size,
        meta={
            "kind": "resume_state",
            "fmt": CKPT_FMT,
            "step": int(np.asarray(host_state["step"])),
            "epoch": int(epoch),
            "best_acc": float(best_acc),
            **({"state_layout": state_layout.tag} if state_layout is not None else {}),
            **(meta or {}),
        },
    )
    if fault_hook is not None:
        fault_hook("post", path)
    return path


def load_resume_state(
    path: str | Path,
    state: TrainState,
    raw_bytes: bytes | None = None,
    info: dict | None = None,
    state_layout=None,
) -> tuple[TrainState, int, float]:
    """Restore ``(state, next_epoch, best_acc)`` from a ``last.ckpt``.

    ``raw_bytes`` lets a caller that already read the file (to verify its
    manifest) restore from the same buffer — one disk read of a possibly
    multi-GB state instead of two.

    The comms error-feedback residual is reconciled across flag
    boundaries (``--ckpt-comms-residual``): restored only when BOTH the
    checkpoint carries one and the restoring state does, with matching
    wire layout (tree + shapes) — any other combination keeps the
    documented drop path (the caller resets to zeros and warns).
    ``info``, when given, gains ``comms_residual``:
    ``"restored"`` / ``"dropped:<why>"`` / ``"absent"``.

    The on-disk trunk stack is canonical (see ``save_resume_state``);
    ``state_layout`` converts it to the restoring run's resident layout
    AFTER restore, so a chunk-resident interleaved run — or a contiguous
    run restoring an old chunk-era checkpoint — gets schedule-shaped
    params/momentum with no caller-side reshaping.  flax restores the
    serialized (canonical) shapes regardless of the template's resident
    shapes, which is exactly what lets one file serve every layout."""
    raw = serialization.msgpack_restore(
        raw_bytes if raw_bytes is not None else Path(path).read_bytes()
    )
    _check_ckpt_fmt(raw, state.params, path)
    template = _state_dict(state)
    raw_state = dict(raw["state"])
    saved_res = raw_state.pop("comms_residual", None)
    want_res = template.pop("comms_residual", None) is not None
    restored = serialization.from_state_dict(template, raw_state)
    if state_layout is not None:
        restored = tree_from_canonical(restored, state_layout)
    residual = None
    note = "absent"
    if saved_res is not None and want_res:
        import jax  # lazy, like every other jax touch in this module

        try:
            candidate = serialization.from_state_dict(
                state.comms_residual, saved_res
            )
            live_shapes = [
                tuple(getattr(l, "shape", ()))
                for l in jax.tree_util.tree_leaves(state.comms_residual)
            ]
            got_shapes = [
                tuple(np.shape(l))
                for l in jax.tree_util.tree_leaves(candidate)
            ]
            if live_shapes == got_shapes:
                residual = candidate
                note = "restored"
            else:
                note = "dropped:wire-layout-changed"
        except (ValueError, KeyError, TypeError):
            note = "dropped:wire-layout-changed"
    elif saved_res is not None:
        note = "dropped:grad-comms-off"
    state = state.replace(
        step=restored["step"],
        params=restored["params"],
        batch_stats=restored["batch_stats"],
        opt_state=restored["opt_state"],
    )
    if residual is not None:
        state = state.replace(comms_residual=residual)
    if info is not None:
        info["comms_residual"] = note
    return state, int(raw["epoch"]) + 1, float(raw["best_acc"])
