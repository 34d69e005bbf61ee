"""Atomic, versioned, resumable checkpoint directories.

Reference counterpart: the reference checkpointed with bare
``NDArray::Save`` to a single file (``model.py save_checkpoint``) — a crash
mid-write truncates the file and loses the run. Here a checkpoint is a
*directory per step* finalized by one atomic ``os.replace`` rename, with a
JSON manifest carrying per-array CRC32 checksums, so the invariant is
binary: a checkpoint directory either exists complete and verified, or it
does not exist at all. Layout::

    <root>/
      step-0000000010/
        manifest.json        # format, step, meta, per-array shape/dtype/crc
        arrays.params        # one dmlc .params container (upstream format)
      step-0000000020/
      .tmp-step-0000000030-<pid>/     # in-flight save (ignored by readers)

Write path: arrays + manifest land in the same-filesystem temp dir, the
temp dir is fsync'd, then renamed into place; retention prunes to the
newest ``keep`` completed steps plus any stale temps. Read path:
:func:`load_checkpoint` verifies the manifest checksums before returning
and :func:`load_latest` walks backwards past corrupt/incomplete steps to
the newest checkpoint that verifies — the resume contract a killed run
needs.

The value layer is intentionally dumb: ``{name: numpy array}`` plus a JSON
``meta`` dict. Trainer integration (pytree gather/reshard, RNG keys,
optimizer state naming) lives with the trainers
(:meth:`parallel.ShardedTrainer.save_checkpoint`,
:meth:`gluon.Trainer.save_checkpoint`).
"""
from __future__ import annotations

import json
import os
import shutil
import time
import warnings
import zlib
from typing import Dict, List, Optional, Tuple

import numpy as onp

from ..base import MXNetError
from . import inject

__all__ = ["save_checkpoint", "load_checkpoint", "load_latest",
           "list_checkpoints", "CheckpointError", "CheckpointCorruptError",
           "FORMAT_VERSION", "ARRAYS_FILE", "MANIFEST_FILE"]

FORMAT_VERSION = 1
ARRAYS_FILE = "arrays.params"
MANIFEST_FILE = "manifest.json"
_STEP_PREFIX = "step-"
_TMP_PREFIX = ".tmp-"
_OLD_SUFFIX = ".replaced"


def _recover(root: str) -> None:
    """Heal a same-step replace that crashed between its two renames: the
    displaced-but-complete old copy sits at ``step-N.replaced`` with no
    ``step-N`` — rename it back so the checkpoint is visible again."""
    if not os.path.isdir(root):
        return
    for name in os.listdir(root):
        if not name.endswith(_OLD_SUFFIX):
            continue
        final = os.path.join(root, name[:-len(_OLD_SUFFIX)])
        old = os.path.join(root, name)
        if _parse_step(name[:-len(_OLD_SUFFIX)]) is None:
            continue
        try:
            if not os.path.isdir(final) \
                    and os.path.isfile(os.path.join(old, MANIFEST_FILE)):
                # reader-side self-heal: idempotent (rename either already
                # happened or is a no-op retry), so every host may run it
                os.replace(old, final)  # mxlint: disable=MX902
            else:
                shutil.rmtree(old, ignore_errors=True)
        except OSError:
            pass  # best-effort; the next reader retries


class CheckpointError(MXNetError):
    """No usable checkpoint (missing directory / no completed steps)."""


class CheckpointCorruptError(CheckpointError):
    """A checkpoint directory exists but fails verification (bad manifest,
    checksum mismatch, truncated arrays file)."""


def _step_dirname(step: int) -> str:
    if step < 0:
        raise CheckpointError(f"checkpoint step must be >= 0, got {step}")
    return f"{_STEP_PREFIX}{step:010d}"


def _parse_step(name: str) -> Optional[int]:
    if not name.startswith(_STEP_PREFIX):
        return None
    try:
        return int(name[len(_STEP_PREFIX):])
    except ValueError:
        return None


def _crc(a: onp.ndarray) -> int:
    return zlib.crc32(onp.ascontiguousarray(a).tobytes()) & 0xFFFFFFFF


def _fsync_dir(path: str) -> None:
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return  # platform without O_RDONLY dirs: rename is still atomic
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def list_checkpoints(root: str) -> List[int]:
    """Completed checkpoint steps under ``root``, ascending. A step counts
    only if its manifest file exists (the last thing a save writes before
    the rename — temp dirs never appear here)."""
    if not os.path.isdir(root):
        return []
    _recover(root)
    steps = []
    for name in os.listdir(root):
        step = _parse_step(name)
        if step is None:
            continue
        if os.path.isfile(os.path.join(root, name, MANIFEST_FILE)):
            steps.append(step)
    return sorted(steps)


def _shard_file(idx: int) -> str:
    return f"arrays-p{idx}.params"


def _marker_file(idx: int) -> str:
    return f"commit-p{idx}.json"


def _commit_timeout_s() -> float:
    try:
        return max(0.1, float(os.environ.get(
            "MXTPU_ELASTIC_COMMIT_TIMEOUT_S", "60")))
    except ValueError:
        return 60.0


def _write_entries(arrays: Dict[str, onp.ndarray]
                   ) -> Tuple[Dict[str, onp.ndarray], Dict[str, dict]]:
    host: Dict[str, onp.ndarray] = {}
    entries: Dict[str, dict] = {}
    for name, a in arrays.items():
        a = onp.asarray(a)
        host[name] = a
        entries[name] = {"shape": list(a.shape), "dtype": a.dtype.name,
                         "crc32": _crc(a)}
    return host, entries


def _write_json(path: str, doc: dict) -> None:
    # callers own the election: paths are either per-host by name (the
    # commit markers) or primary-gated (the manifest) — see
    # _save_multihost, statically unprovable from here
    with open(path, "w") as f:  # mxlint: disable=MX902
        json.dump(doc, f, indent=1, sort_keys=True)
        f.flush()
        os.fsync(f.fileno())


def _finalize_rename(root: str, tmp: str, final: str) -> None:
    inject.crash("checkpoint.finalize")  # died before the atomic rename
    if os.path.isdir(final):
        # same-step replace: os.replace cannot clobber a non-empty dir,
        # so the old copy moves aside first. A crash between the two
        # renames leaves only the aside dir — named so _recover() can
        # rename it back (readers self-heal; the good copy is never in
        # a prunable temp name).
        old = final + _OLD_SUFFIX
        shutil.rmtree(old, ignore_errors=True)   # stale from a crash
        # only the elected primary reaches this helper in a multi-host
        # save (_save_multihost returns early on idx != 0); the single-
        # host path is one writer by construction
        os.replace(final, old)                   # mxlint: disable=MX902
        os.replace(tmp, final)                   # mxlint: disable=MX902
        shutil.rmtree(old, ignore_errors=True)
    else:
        os.replace(tmp, final)                   # mxlint: disable=MX902
    _fsync_dir(root)


def _gather_markers(tmp: str, count: int, timeout_s: float,
                    step: int) -> Dict[int, dict]:
    """Primary-side commit barrier: poll the shared staging dir until
    every host's commit marker exists (each marker is the last thing a
    host fsyncs after its shard). Filesystem polling, not a collective —
    a host that dies mid-shard turns into a loud, attributable timeout
    naming the missing index, never a hang."""
    deadline = time.monotonic() + timeout_s
    missing = list(range(count))
    while True:
        missing = [p for p in range(count)
                   if not os.path.isfile(os.path.join(tmp,
                                                      _marker_file(p)))]
        if not missing:
            break
        if time.monotonic() >= deadline:
            from ..telemetry import flight as _flight
            _flight.dump("checkpoint_commit_timeout",
                         site="checkpoint.manifest", step=step,
                         missing=missing, timeout_s=timeout_s)
            raise CheckpointError(
                f"multi-host checkpoint commit for step {step} timed "
                f"out after {timeout_s:g}s: process(es) {missing} never "
                "wrote their shard commit marker (died mid-shard or "
                "never reached the save) — the torn save stays in its "
                "staging dir, invisible to load_latest")
        time.sleep(0.05)
    markers: Dict[int, dict] = {}
    for p in range(count):
        with open(os.path.join(tmp, _marker_file(p))) as f:
            markers[p] = json.load(f)
    return markers


def _merge_marker_entries(markers: Dict[int, dict],
                          tmp: str, step: int) -> Dict[str, dict]:
    """Merge per-host shard tables into the manifest's array table.
    Overlapping names (replicated params every host gathered) must agree
    bit-for-bit across hosts — a CRC disagreement is SPMD divergence,
    and committing either copy would silently canonize one host's drift:
    refuse loudly instead."""
    merged: Dict[str, dict] = {}
    for p in sorted(markers):
        for name, ent in markers[p].get("arrays", {}).items():
            if name in merged:
                if merged[name]["crc32"] != ent["crc32"]:
                    from ..telemetry import flight as _flight
                    _flight.dump("checkpoint_shard_divergence",
                                 site="checkpoint.manifest", step=step,
                                 array=name, processes=sorted(markers))
                    raise CheckpointError(
                        f"multi-host checkpoint for step {step}: hosts "
                        f"banked DIFFERENT bytes for array {name!r} "
                        f"(crc {merged[name]['crc32']} vs process {p}'s "
                        f"{ent['crc32']}) — SPMD state divergence; "
                        "refusing to commit a manifest that canonizes "
                        "either copy")
                continue
            merged[name] = dict(ent, file=_shard_file(p))
    return merged


def save_checkpoint(root: str, arrays: Dict[str, onp.ndarray],
                    meta: Optional[dict] = None, *, step: int,
                    keep: Optional[int] = 3,
                    process_index: Optional[int] = None,
                    process_count: Optional[int] = None,
                    commit_timeout_s: Optional[float] = None) -> str:
    """Write one atomic checkpoint for ``step``; returns its directory.

    ``arrays`` maps names to host arrays (callers gather device/sharded
    values first); ``meta`` must be JSON-serializable. ``keep`` prunes to
    the newest K completed checkpoints after a successful save (None keeps
    everything). Re-saving an existing step atomically replaces it.

    Multi-host commit protocol (``process_count > 1`` — resolved from
    the live coordination state, or passed explicitly by drills that
    simulate a pod in one process): every host writes its own shard file
    (``arrays-p<idx>.params``) plus a fsync'd commit marker into ONE
    shared staging directory; the elected primary waits for all markers,
    verifies overlapping arrays agree bit-for-bit across hosts, and
    writes the manifest **last**, before the single atomic rename. A
    host killed between its shard write and the primary's manifest
    write leaves a manifest-less staging dir — invisible to
    :func:`load_latest`, so a torn multi-host save can never shadow the
    previous complete step. The marker wait is bounded
    (``MXTPU_ELASTIC_COMMIT_TIMEOUT_S``) and a timeout names the missing
    process index instead of hanging.

    The write runs inside one ``checkpoint.save`` profiler span (a
    host that only writes its shard, or a save that raises, has paid
    that time too); the committing host's successful save also emits a
    ``checkpoint.save`` telemetry event and (when the goodput ledger is
    on) a ``checkpoint`` attribution note — checkpointing is wall time
    the training loop pays, so it must show up in the run's goodput
    vector, not vanish into ``unattributed``.
    """
    from .. import profiler as _prof
    from ..telemetry import events as _tele
    from ..telemetry import goodput as _goodput
    meta = dict(meta or {})
    from ..parallel.dist import world
    widx, wcount = world()
    idx = widx if process_index is None else int(process_index)
    count = wcount if process_count is None else int(process_count)
    final = os.path.join(root, _step_dirname(step))
    if count <= 1:
        # SPMD election (the MX902 invariant): a lone process that still
        # carries a non-zero rank (pre-rendezvous launcher env) must not
        # race the writer it cannot coordinate with — the program does
        # not diverge, only the filesystem effect does.
        from ..parallel.dist import is_primary
        if not is_primary():
            return final
    with _prof.Scope("checkpoint.save") as saved:
        if count > 1:
            path = _save_multihost(root, arrays, meta, step=step,
                                   idx=idx, count=count,
                                   timeout_s=(_commit_timeout_s()
                                              if commit_timeout_s is None
                                              else commit_timeout_s))
            if idx != 0:
                return path
        else:
            os.makedirs(root, exist_ok=True)
            tmp = os.path.join(
                root, f"{_TMP_PREFIX}{_step_dirname(step)}-{os.getpid()}")
            if os.path.isdir(tmp):
                shutil.rmtree(tmp)
            os.makedirs(tmp)
            try:
                host, entries = _write_entries(arrays)
                from ..ndarray.serialization import dmlc_save
                dmlc_save(os.path.join(tmp, ARRAYS_FILE),
                          list(host.values()), list(host.keys()))
                inject.crash("checkpoint.arrays")  # died: arrays, no manifest
                manifest = {"format": FORMAT_VERSION, "step": int(step),
                            "meta": meta, "arrays": entries}
                _write_json(os.path.join(tmp, MANIFEST_FILE), manifest)
                _fsync_dir(tmp)
                _finalize_rename(root, tmp, final)
            except BaseException:
                shutil.rmtree(tmp, ignore_errors=True)
                raise
        if keep is not None:
            _prune(root, keep)
    save_ms = saved.dur_ms
    _tele.emit("checkpoint.save", step=step, wall_ms=round(save_ms, 3),
               path=final, arrays=len(arrays), process_index=idx,
               process_count=count)
    if _goodput.enabled():
        _goodput.note("checkpoint", save_ms)
    return final


def _save_multihost(root: str, arrays: Dict[str, onp.ndarray],
                    meta: dict, *, step: int, idx: int, count: int,
                    timeout_s: float) -> str:
    """The shard half of the commit protocol (every host) plus the
    manifest half (primary only). See :func:`save_checkpoint`."""
    final = os.path.join(root, _step_dirname(step))
    # ONE deterministic staging dir all hosts share (same filesystem as
    # the final name — the rename must stay atomic); no pid suffix, the
    # step dirname IS the coordination key
    tmp = os.path.join(root, f"{_TMP_PREFIX}{_step_dirname(step)}-shared")
    os.makedirs(root, exist_ok=True)
    # every host writes ITS shard + marker; per-host file names make the
    # concurrent writes race-free by construction
    # mxlint rationale: per-host shard files are the protocol — the
    # election applies to the manifest + rename below, not the shards
    os.makedirs(tmp, exist_ok=True)
    host, entries = _write_entries(arrays)
    from ..ndarray.serialization import dmlc_save
    try:
        dmlc_save(os.path.join(tmp, _shard_file(idx)),
                  list(host.values()), list(host.keys()))
        inject.crash("checkpoint.arrays")   # died after shard, no marker
        marker = {"format": FORMAT_VERSION, "step": int(step),
                  "process": {"index": idx, "count": count},
                  "arrays": entries}
        _write_json(os.path.join(tmp, _marker_file(idx)), marker)
        _fsync_dir(tmp)
    except BaseException:
        # a failed host removes only ITS files — peers' shards in the
        # shared staging dir are still the primary's to judge (their
        # absence vs the marker wait is what makes the tear loud)
        for f in (_shard_file(idx), _marker_file(idx)):
            try:
                os.unlink(os.path.join(tmp, f))
            except OSError:
                pass
        raise
    if idx != 0:
        return final
    # the elected primary: wait for every host's marker, verify the
    # shard tables agree, and only THEN write the manifest — the last
    # file before the one atomic rename, so load_latest can never see
    # a torn multi-host save
    try:
        markers = _gather_markers(tmp, count, timeout_s, step)
        merged = _merge_marker_entries(markers, tmp, step)
        inject.crash("checkpoint.manifest")  # died between shards+manifest
        manifest = {"format": FORMAT_VERSION, "step": int(step),
                    "meta": meta, "arrays": merged,
                    "shards": {str(p): {"file": _shard_file(p),
                                        "arrays": sorted(
                                            markers[p]["arrays"])}
                               for p in sorted(markers)}}
        _write_json(os.path.join(tmp, MANIFEST_FILE), manifest)
        _fsync_dir(tmp)
        _finalize_rename(root, tmp, final)
    except BaseException:
        # the primary's failure leaves the manifest-less staging dir in
        # place (peers' shards included): invisible to readers, pruned
        # by the next successful save — the same contract as a SIGKILL
        raise
    return final


def _prune(root: str, keep: int) -> None:
    steps = list_checkpoints(root)
    for step in steps[:-keep] if keep > 0 else steps:
        shutil.rmtree(os.path.join(root, _step_dirname(step)),
                      ignore_errors=True)
    for name in os.listdir(root):
        if name.startswith(_TMP_PREFIX):
            # stale in-flight dirs from crashed saves — never loadable
            shutil.rmtree(os.path.join(root, name), ignore_errors=True)


def load_checkpoint(root: str, step: int,
                    verify: bool = True) -> Tuple[Dict[str, onp.ndarray], dict, int]:
    """Load one step → ``(arrays, meta, step)``; checksum-verifies unless
    ``verify=False``. Raises :class:`CheckpointCorruptError` on any
    mismatch between manifest and arrays."""
    _recover(root)
    path = os.path.join(root, _step_dirname(step))
    mpath = os.path.join(path, MANIFEST_FILE)
    if not os.path.isfile(mpath):
        raise CheckpointError(f"no completed checkpoint for step {step} "
                              f"under {root!r}")
    try:
        with open(mpath) as f:
            manifest = json.load(f)
    except (OSError, ValueError) as e:
        raise CheckpointCorruptError(
            f"{mpath}: unreadable manifest: {e}") from e
    if manifest.get("format") != FORMAT_VERSION:
        raise CheckpointCorruptError(
            f"{mpath}: unsupported checkpoint format "
            f"{manifest.get('format')!r} (this build reads "
            f"{FORMAT_VERSION})")
    from ..ndarray.serialization import dmlc_load
    declared = manifest.get("arrays", {})
    # Group declared names by the container that holds them: single-host
    # manifests carry no per-entry "file" (everything lives in
    # ARRAYS_FILE); multi-host manifests record, per array, the shard of
    # the host that banked it. A shard may hold MORE names than the
    # manifest assigns it (replicated params every host gathered — the
    # merge assigned each to its lowest-index writer); only the assigned
    # names are read from each shard.
    by_file: Dict[str, List[str]] = {}
    for name, ent in declared.items():
        by_file.setdefault(ent.get("file", ARRAYS_FILE), []).append(name)
    if not by_file:
        by_file[ARRAYS_FILE] = []
    arrays: Dict[str, onp.ndarray] = {}
    for fname in sorted(by_file):
        apath = os.path.join(path, fname)
        try:
            values, names = dmlc_load(apath)
        except MXNetError as e:
            raise CheckpointCorruptError(f"{apath}: {e}") from e
        held = dict(zip(names, values))
        missing = [n for n in by_file[fname] if n not in held]
        if missing:
            raise CheckpointCorruptError(
                f"{path}: container {fname} is missing declared "
                f"array(s) {sorted(missing)}")
        if fname == ARRAYS_FILE and "shards" not in manifest:
            # single-host container: strict set equality, exactly the
            # pre-protocol contract
            arrays.update(held)
        else:
            for n in by_file[fname]:
                arrays[n] = held[n]
    if set(arrays) != set(declared):
        raise CheckpointCorruptError(
            f"{path}: manifest declares {sorted(declared)} but arrays file "
            f"holds {sorted(arrays)}")
    for name, ent in declared.items():
        a = arrays[name]
        # the dmlc container promotes 0-d arrays to shape (1,) on the wire
        # (upstream has no 0-d records); the manifest keeps the original
        # shape, so restore it — same bytes, same checksum
        if list(a.shape) != ent["shape"]:
            if a.size == int(onp.prod(ent["shape"], dtype=onp.int64)):
                a = arrays[name] = a.reshape(ent["shape"])
            else:
                raise CheckpointCorruptError(
                    f"{path}: array {name!r} is {a.dtype.name}{a.shape}, "
                    f"manifest says {ent['dtype']}{tuple(ent['shape'])}")
        if verify:
            if a.dtype.name != ent["dtype"]:
                raise CheckpointCorruptError(
                    f"{path}: array {name!r} is {a.dtype.name}{a.shape}, "
                    f"manifest says {ent['dtype']}{tuple(ent['shape'])}")
            if _crc(a) != ent["crc32"]:
                raise CheckpointCorruptError(
                    f"{path}: checksum mismatch for array {name!r}")
    return arrays, manifest.get("meta", {}), int(manifest["step"])


def load_latest(root: str, verify: bool = True
                ) -> Tuple[Dict[str, onp.ndarray], dict, int]:
    """Load the newest checkpoint that verifies, walking backwards past
    corrupt steps (each skip warns). Raises :class:`CheckpointError` when
    nothing under ``root`` is loadable — the caller decides whether a cold
    start is acceptable."""
    steps = list_checkpoints(root)
    if not steps:
        raise CheckpointError(f"no completed checkpoints under {root!r}")
    last_err: Optional[Exception] = None
    for step in reversed(steps):
        try:
            return load_checkpoint(root, step, verify=verify)
        except CheckpointCorruptError as e:
            warnings.warn(f"skipping corrupt checkpoint step {step}: {e}")
            last_err = e
    raise CheckpointError(
        f"all {len(steps)} checkpoints under {root!r} failed verification; "
        f"last error: {last_err}")
