"""repro.core.persist — atomic, crash-consistent persistence primitives.

Every on-disk artifact the stack commits to (training checkpoints, serving
snapshots, traces, bench histories) goes through this module, so the
crash-consistency rules live in exactly one place:

  1. WRITE-NEW, NEVER IN-PLACE: content lands in a temp file/dir in the
     SAME directory as the destination (same filesystem, so the final
     ``os.replace``/``rename`` is atomic), is fsynced, then renamed over
     the destination.  A crash at any point leaves either the old artifact
     or the new one — never a torn hybrid (contrast: a crash inside
     ``np.savez`` produces exactly the truncated npz
     `faults.corrupt_trace_npz` simulates).
  2. MANIFEST LAST: multi-file artifacts (pytree snapshots) write their
     payload shards first and the manifest — which carries a CRC32 per
     shard — last, inside the temp dir; the rename publishes all of it at
     once, and the ``LATEST`` pointer flips only after the directory is
     durable.
  3. VALIDATE ON LOAD: `validate_step` re-checks manifest/shard
     consistency (missing shard, truncated shard, CRC mismatch, stale
     manifest naming files that do not exist) and raises a typed
     `SnapshotCorruptError` — a half-loaded snapshot is never returned.
     `newest_valid_step` walks steps newest-first and skips corrupt ones,
     which is the serving tier's recovery rule: load the newest snapshot
     that VALIDATES, not the newest directory that exists.

`train/checkpoint.py` (1-GiB-sharded training checkpoints with elastic
resharding) and `serve/durability.py` (scheduler/engine snapshots under
the write-ahead log) are both thin layers over `save_tree`/`load_tree`.
"""

from __future__ import annotations

import json
import os
import shutil
import tempfile
import time
import zlib
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.core.errors import SnapshotCorruptError

SHARD_BYTES = 1 << 30  # 1 GiB per npz shard (train checkpoint default)


# ---------------------------------------------------------------------------
# single-file atomic writes
# ---------------------------------------------------------------------------


def fsync_file(path: Path | str) -> None:
    fd = os.open(str(path), os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def fsync_dir(path: Path | str) -> None:
    """Durably record a directory entry (the rename itself) — without this
    the atomic replace can be undone by a crash even though the file data
    survived."""
    fd = os.open(str(path), os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def atomic_write_bytes(path: Path | str, blob: bytes, *,
                       fsync: bool = True) -> Path:
    """tmp + fsync + os.replace: the destination is either the old content
    or the complete new content, never a truncated mix."""
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    fd, tmp = tempfile.mkstemp(
        prefix=f".{path.name}.", suffix=".tmp", dir=path.parent
    )
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(blob)
            f.flush()
            if fsync:
                os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    if fsync:
        fsync_dir(path.parent)
    return path


def atomic_write_text(path: Path | str, text: str, *,
                      fsync: bool = True) -> Path:
    return atomic_write_bytes(path, text.encode("utf-8"), fsync=fsync)


def atomic_write_json(path: Path | str, obj: Any, *, fsync: bool = True,
                      indent: Optional[int] = None) -> Path:
    return atomic_write_text(
        path, json.dumps(obj, indent=indent) + "\n", fsync=fsync
    )


def atomic_savez(path: Path | str, *, compressed: bool = False,
                 fsync: bool = True, **arrays: np.ndarray) -> Path:
    """Atomic `np.savez[_compressed]`.  Mirrors numpy's name handling (a
    missing ``.npz`` suffix is appended) so callers can swap it in for
    `np.savez` without changing the paths they later `np.load`."""
    path = Path(path)
    if path.suffix != ".npz":
        path = path.with_name(path.name + ".npz")
    import io

    buf = io.BytesIO()
    (np.savez_compressed if compressed else np.savez)(buf, **arrays)
    return atomic_write_bytes(path, buf.getvalue(), fsync=fsync)


# ---------------------------------------------------------------------------
# manifest-directory pytree snapshots (generalized from train/checkpoint.py)
# ---------------------------------------------------------------------------


def flatten_with_paths(tree) -> Tuple[List[str], list, Any]:
    flat, treedef = jax.tree.flatten_with_path(tree)
    paths = ["/".join(str(k) for k in path) for path, _ in flat]
    leaves = [leaf for _, leaf in flat]
    return paths, leaves, treedef


def _host_leaf(x) -> Tuple[np.ndarray, str]:
    """(storable array, original dtype tag).  npz can't serialize ml_dtypes
    (bf16 etc.) — store as f32 + dtype tag; load casts back."""
    arr = np.asarray(x)
    tag = str(arr.dtype)
    if arr.dtype.kind not in "fiub" or tag == "bfloat16":
        arr = arr.astype(np.float32)
    return arr, tag


def step_dir(root: Path | str, step: int, prefix: str = "step") -> Path:
    return Path(root) / f"{prefix}_{step}"


def save_tree(
    root: Path | str,
    step: int,
    tree: Any,
    *,
    extra: Optional[Dict[str, Any]] = None,
    fsync: bool = True,
    prefix: str = "step",
    shard_bytes: int = SHARD_BYTES,
) -> Path:
    """Write ``<root>/<prefix>_<step>/`` (shards + manifest) atomically and
    flip ``<root>/LATEST`` to it.  `extra` is an arbitrary JSON-able dict
    stored inside the manifest — the serving snapshot keeps its host-side
    scheduler/engine state there, next to the array shards."""
    root = Path(root)
    root.mkdir(parents=True, exist_ok=True)
    paths, leaves, _ = flatten_with_paths(tree)
    host_leaves, dtypes = [], []
    for x in leaves:
        arr, tag = _host_leaf(x)
        host_leaves.append(arr)
        dtypes.append(tag)

    tmp = root / f".tmp_{prefix}_{step}_{os.getpid()}"
    if tmp.exists():
        shutil.rmtree(tmp)
    tmp.mkdir(parents=True)
    shards, cur, cur_bytes, idx = [], {}, 0, {}
    for name, arr in zip(paths, host_leaves):
        key = f"leaf_{len(cur)}"
        cur[key] = arr
        idx[name] = (len(shards), key)
        cur_bytes += arr.nbytes
        if cur_bytes >= shard_bytes:
            shards.append(cur)
            cur, cur_bytes = {}, 0
    shards.append(cur)
    shard_crc = []
    for i, sh in enumerate(shards):
        p = tmp / f"shard_{i}.npz"
        np.savez(p, **sh)
        shard_crc.append(zlib.crc32(p.read_bytes()) & 0xFFFFFFFF)
        if fsync:
            fsync_file(p)
    manifest = {
        "step": step,
        "leaves": {n: list(v) for n, v in idx.items()},
        "dtypes": dict(zip(paths, dtypes)),
        "n_shards": len(shards),
        "shard_crc": shard_crc,
        "extra": extra if extra is not None else {},
        "time": time.time(),
    }
    mpath = tmp / "manifest.json"
    mpath.write_text(json.dumps(manifest))
    if fsync:
        fsync_file(mpath)
    final = step_dir(root, step, prefix)
    if final.exists():
        shutil.rmtree(final)
    tmp.rename(final)
    if fsync:
        fsync_dir(root)
    atomic_write_text(root / "LATEST", final.name, fsync=fsync)
    return final


def latest_step(root: Path | str, prefix: str = "step") -> Optional[int]:
    """The step the LATEST pointer names — without validating it (use
    `newest_valid_step` when the directory may have been damaged)."""
    p = Path(root) / "LATEST"
    if not p.exists():
        return None
    name = p.read_text().strip()
    try:
        return int(name.rsplit("_", 1)[1])
    except (IndexError, ValueError):
        return None


def available_steps(root: Path | str, prefix: str = "step") -> List[int]:
    """All on-disk step numbers under root, descending (newest first)."""
    root = Path(root)
    if not root.exists():
        return []
    out = []
    for d in root.iterdir():
        if d.is_dir() and d.name.startswith(f"{prefix}_"):
            try:
                out.append(int(d.name.rsplit("_", 1)[1]))
            except ValueError:
                continue
    return sorted(out, reverse=True)


def validate_step(root: Path | str, step: int,
                  prefix: str = "step") -> Dict[str, Any]:
    """Check one snapshot directory end to end; return its manifest or
    raise `SnapshotCorruptError` (missing/unparseable manifest, missing
    shard, shard CRC mismatch, manifest naming leaves its shards lack)."""
    d = step_dir(root, step, prefix)
    mpath = d / "manifest.json"
    if not mpath.exists():
        raise SnapshotCorruptError("manifest.json missing", path=str(d))
    try:
        manifest = json.loads(mpath.read_text())
    except (ValueError, OSError) as e:
        raise SnapshotCorruptError(
            f"unreadable manifest ({e})", path=str(d)
        ) from e
    n = manifest.get("n_shards")
    crcs = manifest.get("shard_crc")
    if not isinstance(n, int) or n < 1:
        raise SnapshotCorruptError("manifest lacks n_shards", path=str(d))
    for i in range(n):
        p = d / f"shard_{i}.npz"
        if not p.exists():
            raise SnapshotCorruptError(
                f"shard_{i}.npz missing", path=str(d)
            )
        if crcs is not None:
            got = zlib.crc32(p.read_bytes()) & 0xFFFFFFFF
            if got != crcs[i]:
                raise SnapshotCorruptError(
                    f"shard_{i}.npz CRC mismatch "
                    f"(manifest {crcs[i]:#x}, file {got:#x})",
                    path=str(d),
                )
    for name, (shard_i, _key) in manifest.get("leaves", {}).items():
        if not isinstance(shard_i, int) or shard_i >= n:
            raise SnapshotCorruptError(
                f"stale manifest: leaf {name!r} names shard {shard_i} "
                f"of {n}", path=str(d),
            )
    return manifest


def newest_valid_step(root: Path | str,
                      prefix: str = "step") -> Optional[int]:
    """Newest step that VALIDATES: tries the LATEST pointer first, then
    every on-disk step newest-first, skipping corrupt ones.  None when no
    valid snapshot exists (recovery then starts from a fresh init)."""
    candidates = available_steps(root, prefix)
    pointed = latest_step(root, prefix)
    if pointed is not None and pointed in candidates:
        candidates.remove(pointed)
        candidates.insert(0, pointed)
    elif pointed is not None:
        # stale LATEST: points at a step that is not on disk — fall
        # through to the scan
        pass
    for step in candidates:
        try:
            validate_step(root, step, prefix)
            return step
        except SnapshotCorruptError:
            continue
    return None


def load_tree(
    root: Path | str,
    like: Any,
    step: Optional[int] = None,
    *,
    prefix: str = "step",
    place: Optional[Callable[[int, np.ndarray, Any], Any]] = None,
    validate: bool = True,
) -> Tuple[Any, Dict[str, Any]]:
    """Load a snapshot into the structure of `like` (pytree of arrays or
    ShapeDtypeStructs); returns ``(tree, manifest)``.  `place(i, arr,
    leaf)` maps each loaded numpy leaf onto its device/dtype target — the
    default casts to the `like` leaf's dtype and wraps in `jnp.asarray`
    (train/checkpoint.py passes a sharding-aware placer)."""
    root = Path(root)
    if step is None:
        step = latest_step(root, prefix)
        if step is None:
            raise FileNotFoundError(f"no LATEST in {root}")
    manifest = (
        validate_step(root, step, prefix) if validate
        else json.loads((step_dir(root, step, prefix)
                         / "manifest.json").read_text())
    )
    d = step_dir(root, step, prefix)
    shard_cache: Dict[int, Any] = {}

    paths, leaves, treedef = flatten_with_paths(like)
    out = []
    for i, (name, leaf) in enumerate(zip(paths, leaves)):
        if name not in manifest["leaves"]:
            raise SnapshotCorruptError(
                f"manifest lacks leaf {name!r}", path=str(d)
            )
        shard_i, key = manifest["leaves"][name]
        if shard_i not in shard_cache:
            try:
                shard_cache[shard_i] = np.load(d / f"shard_{shard_i}.npz")
            except Exception as e:
                raise SnapshotCorruptError(
                    f"unreadable shard_{shard_i}.npz "
                    f"({type(e).__name__}: {e})", path=str(d),
                ) from e
        arr = shard_cache[shard_i][key]
        if place is not None:
            out.append(place(i, arr, leaf))
        else:
            if hasattr(leaf, "dtype") and arr.dtype != leaf.dtype:
                arr = arr.astype(leaf.dtype)
            out.append(jax.numpy.asarray(arr))
    return jax.tree.unflatten(treedef, out), manifest


def prune_steps(root: Path | str, keep: int, prefix: str = "step") -> int:
    """Delete all but the newest `keep` snapshot dirs; returns the number
    removed.  Never removes the step LATEST points at."""
    steps = available_steps(root, prefix)
    pointed = latest_step(root, prefix)
    removed = 0
    for step in steps[max(keep, 1):]:
        if step == pointed:
            continue
        shutil.rmtree(step_dir(root, step, prefix), ignore_errors=True)
        removed += 1
    return removed


__all__ = [
    "SHARD_BYTES",
    "fsync_file", "fsync_dir",
    "atomic_write_bytes", "atomic_write_text", "atomic_write_json",
    "atomic_savez",
    "flatten_with_paths", "step_dir", "save_tree", "load_tree",
    "latest_step", "available_steps", "newest_valid_step",
    "validate_step", "prune_steps",
]
