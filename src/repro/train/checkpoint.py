"""Checkpointing + fault tolerance.

Design for 1000+ nodes (scaled-down faithfully here):
  * step-sharded directories ``<dir>/step_<n>/`` written atomically
    (tmp dir + rename) so a mid-write failure never corrupts the latest
    complete checkpoint;
  * one ``.npz`` per host with that host's addressable shards plus a JSON
    manifest (step, mesh shape, leaf paths/shapes/dtypes, RNG, config
    fingerprint) — restore works on a DIFFERENT mesh (elastic re-shard:
    arrays are re-placed through device_put with the new sharding);
  * **crash safety**: every durable write is fsync'd (shard, manifest,
    the containing directory, the ``latest`` pointer — which is itself
    updated via write-to-temp + ``os.replace``), and the manifest carries
    a per-array blake2b checksum.  A torn step dir (kill mid-write) or a
    corrupt one (bit rot, truncation) is DETECTED — ``latest_step`` skips
    dirs whose manifest/shard are incomplete, and the restore paths verify
    checksums and fall back to the newest older step that passes instead
    of loading garbage (:class:`CheckpointCorruptError` when none does);
  * ``keep_last`` garbage collection that never deletes a step currently
    being restored and never deletes the only complete step;
  * deterministic resume: the data pipeline keys off (seed, step), so a
    restart reproduces the exact batch order (see repro.data.tokens).

Fault-injection points (``repro.testing.faults``) bracket every durable
transition of the save path; injected faults deliberately skip the tmp-dir
cleanup so the on-disk debris matches a hard kill, and stale tmp dirs are
swept by the next writer.

On this single-process container there is exactly one host shard; the
multihost path writes ``shard_<process_index>.npz`` per host — same format.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import shutil
import tempfile
import time
import zipfile
import zlib
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.testing import faults

PyTree = Any

MANIFEST_VERSION = 2        # v2 adds per-leaf checksums; v1 restores fine

# step dirs currently being restored (abspaths): _gc must not delete them
_RESTORING: set = set()

# (ckpt_dir, skipped step) pairs recorded when a restore fell back past a
# torn/corrupt step — observability for serving-side degradation counters
_FALLBACK_LOG: List[Tuple[str, int]] = []


class CheckpointCorruptError(RuntimeError):
    """A step dir failed verification (torn write, checksum mismatch)."""


def fallback_log() -> List[Tuple[str, int]]:
    """Steps skipped as corrupt by restore fallbacks since process start."""
    return list(_FALLBACK_LOG)


def _note_fallback(ckpt_dir: str, skipped: List[int]) -> None:
    """Record steps a restore skipped as corrupt: the module log (exact
    (dir, step) pairs for debugging) AND the metrics registry (the counter
    operators watch — silent fallbacks were invisible before PR 7)."""
    _FALLBACK_LOG.extend((ckpt_dir, int(s)) for s in skipped)
    obs.metrics.counter("checkpoint.fallback_steps").inc(len(skipped))


def _flatten_with_paths(tree: PyTree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    paths = ["/".join(str(k) for k in path) for path, _ in flat]
    leaves = [leaf for _, leaf in flat]
    return paths, leaves, treedef


def _step_name(step: int) -> str:
    return f"step_{step:08d}"


def _leaf_digest(raw: bytes) -> str:
    return hashlib.blake2b(raw, digest_size=16).hexdigest()


def _fsync_path(path: str) -> None:
    """fsync a file or directory by path (best-effort on exotic fs)."""
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def _sweep_stale_tmp(ckpt_dir: str) -> None:
    """Remove tmp dirs left by a killed writer (single-writer protocol)."""
    for d in os.listdir(ckpt_dir):
        if d.startswith(".tmp_step_"):
            shutil.rmtree(os.path.join(ckpt_dir, d), ignore_errors=True)


def save_checkpoint(ckpt_dir: str, step: int, tree: PyTree,
                    extra: Optional[Dict[str, Any]] = None,
                    keep_last: int = 3) -> str:
    """Atomic, fsync'd, checksummed save.  Returns the final step directory.

    Kill this at ANY point and the directory still holds only complete,
    verifiable steps: the shard and manifest land in a tmp dir, are
    fsync'd, and become visible in one ``rename``; the ``latest`` pointer
    is advisory (readers fall back to directory listing when it is stale
    or torn).
    """
    t_save = time.perf_counter()
    with obs.tracer.annotate("checkpoint.save"):
        os.makedirs(ckpt_dir, exist_ok=True)
        _sweep_stale_tmp(ckpt_dir)
        paths, leaves, _ = _flatten_with_paths(tree)
        host = jax.process_index()

        final = os.path.join(ckpt_dir, _step_name(step))
        tmp = tempfile.mkdtemp(dir=ckpt_dir, prefix=f".tmp_step_{step}_")
        try:
            faults.fire("checkpoint.save.pre_shard", step=step)
            # raw-byte storage: npz cannot roundtrip ml_dtypes (bf16/fp8);
            # shapes and true dtypes live in the manifest
            raw = [np.ascontiguousarray(np.asarray(l)).tobytes()
                   for l in leaves]
            arrays = {f"leaf_{i}": np.frombuffer(b, np.uint8)
                      for i, b in enumerate(raw)}
            shard_path = os.path.join(tmp, f"shard_{host}.npz")
            np.savez(shard_path, **arrays)
            _fsync_path(shard_path)
            faults.fire("checkpoint.save.post_shard", step=step)
            manifest = {
                "manifest_version": MANIFEST_VERSION,
                "step": step,
                "n_leaves": len(leaves),
                "paths": paths,
                "shapes": [list(np.shape(l)) for l in leaves],
                "dtypes": [str(np.asarray(l).dtype) for l in leaves],
                "checksums": [_leaf_digest(b) for b in raw],
                "n_processes": jax.process_count(),
                "extra": extra or {},
            }
            man_path = os.path.join(tmp, "manifest.json")
            with open(man_path, "w") as f:
                json.dump(manifest, f)
                f.flush()
                os.fsync(f.fileno())
            _fsync_path(tmp)
            faults.fire("checkpoint.save.pre_rename", step=step)
            if os.path.exists(final):
                shutil.rmtree(final)
            os.rename(tmp, final)
            _fsync_path(ckpt_dir)
        except BaseException as e:
            # an InjectedFault emulates SIGKILL: leave the debris on disk so the
            # recovery path is tested against what a real kill leaves behind
            if not isinstance(e, faults.InjectedFault):
                shutil.rmtree(tmp, ignore_errors=True)
            raise
        faults.fire("checkpoint.save.post_rename", step=step)

        # advisory pointer, atomically replaced (a reader never sees a torn
        # pointer file; a STALE one is handled by the listing fallback)
        ptr_tmp = os.path.join(ckpt_dir, ".latest.tmp")
        with open(ptr_tmp, "w") as f:
            f.write(_step_name(step))
            f.flush()
            os.fsync(f.fileno())
        os.replace(ptr_tmp, os.path.join(ckpt_dir, "latest"))
        _fsync_path(ckpt_dir)
        faults.fire("checkpoint.save.post_latest", step=step)

        _gc(ckpt_dir, keep_last)
        t_done = time.perf_counter()
    obs.tracer.record("checkpoint.save", t_save, t_done)
    obs.metrics.counter("checkpoint.saves").inc()
    if t_done > t_save:
        nbytes = sum(len(b) for b in raw)
        obs.metrics.gauge("checkpoint.save_mbps").set(
            nbytes / (t_done - t_save) / 1e6)
    return final


# ----------------------------------------------------------- verification
def _read_manifest(step_dir: str) -> Optional[Dict[str, Any]]:
    """Parse a step dir's manifest; None when missing/torn."""
    try:
        with open(os.path.join(step_dir, "manifest.json")) as f:
            m = json.load(f)
        for k in ("step", "n_leaves", "paths", "shapes", "dtypes"):
            if k not in m:
                return None
        return m
    except (OSError, ValueError):
        return None


def _quick_ok(step_dir: str) -> Optional[Dict[str, Any]]:
    """Cheap completeness check: manifest parses + this host's shard file
    exists.  Payload integrity (checksums) is verified on restore."""
    m = _read_manifest(step_dir)
    if m is None:
        return None
    shard = os.path.join(step_dir, f"shard_{jax.process_index()}.npz")
    return m if os.path.exists(shard) else None


def list_steps(ckpt_dir: str) -> List[int]:
    """Ascending step numbers of COMPLETE (quick-verified) step dirs."""
    if not os.path.isdir(ckpt_dir):
        return []
    out = []
    for d in sorted(os.listdir(ckpt_dir)):
        if not d.startswith("step_"):
            continue
        if _quick_ok(os.path.join(ckpt_dir, d)) is not None:
            try:
                out.append(int(d.split("_")[1]))
            except ValueError:
                continue
    return out


def verify_step(ckpt_dir: str, step: int) -> bool:
    """Deep verification: manifest + shard + per-leaf byte sizes and
    checksums (manifest v2; v1 checks sizes only).  Reads the payload."""
    step_dir = os.path.join(ckpt_dir, _step_name(step))
    m = _quick_ok(step_dir)
    if m is None:
        return False
    try:
        _read_leaves(step_dir, m)
    except CheckpointCorruptError:
        return False
    return True


def _read_leaves(step_dir: str, manifest: Dict[str, Any]) -> List[np.ndarray]:
    """Load + verify this host's leaves; raises CheckpointCorruptError."""
    shard = os.path.join(step_dir, f"shard_{jax.process_index()}.npz")
    checksums = manifest.get("checksums")
    leaves = []
    try:
        with np.load(shard) as data:
            names = set(data.files)
            for i in range(manifest["n_leaves"]):
                key = f"leaf_{i}"
                if key not in names:
                    raise CheckpointCorruptError(
                        f"{shard}: missing {key} "
                        f"(has {len(names)}/{manifest['n_leaves']} leaves)")
                raw = data[key].tobytes()
                dt = np.dtype(manifest["dtypes"][i])
                shape = tuple(manifest["shapes"][i])
                want = int(np.prod(shape, dtype=np.int64)) * dt.itemsize
                if len(raw) != want:
                    raise CheckpointCorruptError(
                        f"{shard}: leaf_{i} holds {len(raw)} bytes, manifest "
                        f"says {want} ({shape}, {dt}) — truncated write?")
                if checksums is not None and _leaf_digest(raw) != checksums[i]:
                    raise CheckpointCorruptError(
                        f"{shard}: leaf_{i} checksum mismatch — corrupt "
                        f"payload (path {manifest['paths'][i]!r})")
                leaves.append(np.frombuffer(raw, dt).reshape(shape))
    except (OSError, ValueError, zipfile.BadZipFile, zlib.error,
            KeyError) as e:
        # a torn zip (truncated shard) or a CRC failure during member
        # decompression lands here
        raise CheckpointCorruptError(f"{shard}: unreadable shard ({e})")
    return leaves


def _gc(ckpt_dir: str, keep_last: int) -> None:
    """Delete old step dirs, with two guards that make GC safe to run at
    any moment: a step currently being restored is never deleted, and the
    newest COMPLETE step always survives (even when ``keep_last`` newer —
    but torn — dirs exist above it, the one good step must not be lost)."""
    steps = sorted(d for d in os.listdir(ckpt_dir) if d.startswith("step_"))
    if keep_last <= 0:
        return
    victims = list(steps[:-keep_last])
    complete = {d for d in steps
                if _quick_ok(os.path.join(ckpt_dir, d)) is not None}
    surviving_complete = [d for d in steps
                          if d in complete and d not in victims]
    if not surviving_complete:
        for d in reversed(victims):         # spare the newest complete victim
            if d in complete:
                victims.remove(d)
                break
    for d in victims:
        path = os.path.join(ckpt_dir, d)
        if os.path.abspath(path) in _RESTORING:
            continue
        shutil.rmtree(path, ignore_errors=True)


def latest_step(ckpt_dir: str) -> Optional[int]:
    """Newest COMPLETE step.  The ``latest`` pointer is advisory: when it
    is missing, torn, or names an incomplete dir, fall back to the newest
    step dir that passes the completeness check."""
    if not os.path.isdir(ckpt_dir):
        return None
    ptr = os.path.join(ckpt_dir, "latest")
    if os.path.exists(ptr):
        try:
            with open(ptr) as f:
                name = f.read().strip()
            if name.startswith("step_") and \
                    _quick_ok(os.path.join(ckpt_dir, name)) is not None:
                return int(name.split("_")[1])
        except (OSError, ValueError):
            pass
    steps = list_steps(ckpt_dir)
    return steps[-1] if steps else None


def peek_manifest(ckpt_dir: str, step: Optional[int] = None) -> Dict[str, Any]:
    """Read a checkpoint's manifest without touching the array payload.

    Lets self-describing consumers (e.g. ``repro.serve.model_bank``) build a
    restore target from the stored paths/shapes/dtypes instead of having to
    know them up front — a cold-starting server has nothing but the
    directory.
    """
    if step is None:
        step = latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError(f"no checkpoint under {ckpt_dir}")
    m = _read_manifest(os.path.join(ckpt_dir, _step_name(step)))
    if m is None:
        raise CheckpointCorruptError(
            f"{ckpt_dir}/{_step_name(step)}: manifest missing or torn")
    return m


def restore_self_describing(ckpt_dir: str, step: Optional[int] = None
                            ) -> Tuple[Dict[str, np.ndarray], Dict[str, Any]]:
    """Restore a FLAT-dict checkpoint with the target built from its own
    manifest — for consumers that have nothing but the directory (model
    banks, stage artifacts).  Returns ``({key: np.ndarray}, extra)``.

    Only valid for checkpoints whose tree was a flat ``{str: array}`` dict
    (every stage artifact in this repo); the manifest path strings are the
    dict keys.  With ``step=None`` a corrupt newest step is SKIPPED and the
    next older complete step is tried (logged in :func:`fallback_log`); an
    explicit ``step`` raises instead.
    """
    candidates = ([step] if step is not None
                  else list(reversed(list_steps(ckpt_dir))))
    if not candidates:
        raise FileNotFoundError(f"no checkpoint under {ckpt_dir}")
    last_err: Optional[Exception] = None
    for i, s in enumerate(candidates):
        try:
            manifest = peek_manifest(ckpt_dir, s)
            target = {}
            for path, dt in zip(manifest["paths"], manifest["dtypes"]):
                target[path.strip("[]'\"")] = np.zeros((), dtype=np.dtype(dt))
            tree, _, extra = restore_checkpoint(ckpt_dir, target, step=s)
            if i > 0:
                _note_fallback(ckpt_dir, candidates[:i])
            return {k: np.asarray(v) for k, v in tree.items()}, extra
        except CheckpointCorruptError as e:
            if step is not None:
                raise
            last_err = e
    raise CheckpointCorruptError(
        f"{ckpt_dir}: no step survived verification "
        f"(tried {candidates}; last error: {last_err})")


def restore_checkpoint(ckpt_dir: str, target: PyTree,
                       step: Optional[int] = None,
                       shardings: Optional[PyTree] = None
                       ) -> Tuple[PyTree, int, Dict[str, Any]]:
    """Restore into the structure of ``target``.

    ``shardings`` (a NamedSharding tree congruent with target) enables
    elastic re-meshing: the stored host arrays are re-placed under the NEW
    mesh regardless of the mesh they were saved from.

    Integrity: per-leaf byte sizes and (manifest v2) checksums are verified
    as the payload is read; a torn or corrupt step raises
    :class:`CheckpointCorruptError`.  With ``step=None`` the newest
    complete step is restored and corrupt steps are skipped in favour of
    the next older one (the skip is recorded in :func:`fallback_log`); an
    explicit ``step`` fails fast instead.
    """
    candidates = ([step] if step is not None
                  else list(reversed(list_steps(ckpt_dir))))
    if not candidates:
        raise FileNotFoundError(f"no checkpoint under {ckpt_dir}")
    last_err: Optional[Exception] = None
    for i, s in enumerate(candidates):
        try:
            out = _restore_one(ckpt_dir, target, int(s), shardings)
            if i > 0:
                _note_fallback(ckpt_dir, candidates[:i])
            return out
        except CheckpointCorruptError as e:
            if step is not None:
                raise
            last_err = e
    raise CheckpointCorruptError(
        f"{ckpt_dir}: no step survived verification "
        f"(tried {candidates}; last error: {last_err})")


def _restore_one(ckpt_dir: str, target: PyTree, step: int,
                 shardings: Optional[PyTree]
                 ) -> Tuple[PyTree, int, Dict[str, Any]]:
    d = os.path.join(ckpt_dir, _step_name(step))
    _RESTORING.add(os.path.abspath(d))
    t_restore = time.perf_counter()
    try:
        with obs.tracer.annotate("checkpoint.restore"):
            manifest = _read_manifest(d)
            if manifest is None:
                raise CheckpointCorruptError(f"{d}: manifest missing or torn")
            leaves = _read_leaves(d, manifest)
            t_read = time.perf_counter()
        obs.tracer.record("checkpoint.restore", t_restore, t_read)
        obs.metrics.counter("checkpoint.restores").inc()
        if t_read > t_restore:
            obs.metrics.gauge("checkpoint.restore_mbps").set(
                sum(l.nbytes for l in leaves) / (t_read - t_restore) / 1e6)
        faults.fire("checkpoint.restore.mid", step=step)

        t_paths, t_leaves, treedef = _flatten_with_paths(target)
        if t_paths != manifest["paths"]:
            raise ValueError(
                "checkpoint/target structure mismatch:\n"
                f"  missing: {set(manifest['paths']) - set(t_paths)}\n"
                f"  extra:   {set(t_paths) - set(manifest['paths'])}")

        out = []
        for leaf, tgt in zip(leaves, t_leaves):
            arr = jnp.asarray(leaf, dtype=tgt.dtype)
            out.append(arr)
        tree = jax.tree.unflatten(treedef, out)
        if shardings is not None:
            tree = jax.tree.map(lambda x, s: jax.device_put(x, s),
                                tree, shardings)
        return tree, step, manifest["extra"]
    finally:
        _RESTORING.discard(os.path.abspath(d))
