"""Weight snapshot store: pre-laid-out parameters for zero-transform cold loads.

The paper's observation that interpreted functions (Python + scipy) pay ~80 ms extra
at start maps here: a *generic* checkpoint needs parse + cast + reshard work in the
start path, while a *snapshot* is written at deploy time in exactly the layout the
executor consumes (target dtype, target shard layout), so a start moves bytes and
nothing else.

Two on-disk formats:

v1 (standalone stores, e.g. repro.checkpoint):
    <root>/<name>/index.json         tree structure + shapes/dtypes
    <root>/<name>/leaf_00000.npy ... one file per pytree leaf
    ``load(mmap_mode='r')`` maps the files; bytes hit memory lazily during
    device_put — the closest CPU analogue of DMA-ing straight into HBM.

v2 (chunked; active whenever a ``blobs`` ChunkStore is attached — the Gateway
always attaches one):
    <root>/<name>/index.json         tree structure + per-leaf CHUNK MANIFEST
    <blobs>/<id[:2]>/<id>.chunk      content-addressed chunks, SHARED across
                                     snapshots (refcounted in the ChunkStore)
    ``save`` splits each leaf's raw bytes into fixed-size BLAKE2-addressed
    chunks; equal content (two configs sharing base weights, an unchanged
    leaf across versions) is stored once. A restore with a host chunk tier
    becomes a DELTA restore (repro.core.blobstore.delta_restore): only the
    chunks the host doesn't already hold move over the wire.

    v2.1 adds an optional ``first_use_order`` list (leaf paths in execution
    first-touch order, from deploy-time profiling): ``leaf_order`` /
    ``iter_restore`` / ``assemble_tree`` fetch leaves in that order so a
    streamed restore makes the head of the model runnable first. Advisory
    only — v2.0 readers ignore it, and leaves always land at their ordinal.

Invariants: ``save`` publishes atomically (a reader never sees a partial
snapshot); v2 chunk refcounts are balanced — one incref per unique chunk per
save, one decref per evict/overwrite — so shared chunks outlive any single
snapshot; the index always records the LOGICAL dtype (bf16/fp8), with storage
in a same-width uint view where numpy's .npy/raw formats would degrade it.
"""
from __future__ import annotations

import json
import os
import shutil
import threading
from pathlib import Path
from typing import Any, Callable, Dict, List, Tuple

import jax
import ml_dtypes
import numpy as np


def _flatten_with_paths(tree) -> Tuple[List[Tuple[str, Any]], Any]:
    flat, treedef = jax.tree.flatten_with_path(tree)
    items = [(jax.tree_util.keystr(path), leaf) for path, leaf in flat]
    return items, treedef


_RAW_VIEWS = {1: np.uint8, 2: np.uint16, 4: np.uint32, 8: np.uint64}


def _resolve_dtype(name: str) -> np.dtype:
    try:
        return np.dtype(name)
    except TypeError:
        return np.dtype(getattr(ml_dtypes, name))      # bfloat16, float8_*, ...


def _is_native(dt: np.dtype) -> bool:
    """True if np.save/np.load round-trips this dtype faithfully.

    ``np.dtype(str(dt))`` is not the right probe: ml_dtypes registers its type
    names with numpy, so that round-trips even though the .npy *format* header
    degrades bf16/fp8 to void (or rejects them outright).  Probe the actual
    header descr round-trip instead."""
    try:
        from numpy.lib import format as npy_format
        return npy_format.descr_to_dtype(npy_format.dtype_to_descr(dt)) == dt
    except (TypeError, ValueError):
        return False


def _to_storable(arr: np.ndarray) -> Tuple[np.ndarray, str]:
    """numpy serializes ml_dtypes (bf16 etc.) as void — store a same-width uint view."""
    if _is_native(arr.dtype):
        return arr, str(arr.dtype)
    return arr.view(_RAW_VIEWS[arr.dtype.itemsize]), str(arr.dtype)


def _from_storable(arr: np.ndarray, logical_dtype: str) -> np.ndarray:
    dt = _resolve_dtype(logical_dtype)
    if arr.dtype == dt:
        return arr
    return arr.view(dt)


class SnapshotStore:
    def __init__(self, root: str | Path, blobs=None) -> None:
        """``blobs`` is a repro.core.blobstore.ChunkStore; when attached,
        ``save`` writes the v2 chunked format (content-addressed, dedup'd,
        delta-restorable) instead of per-leaf .npy files."""
        self.root = Path(root)
        self.root.mkdir(parents=True, exist_ok=True)
        self.blobs = blobs
        self._lock = threading.Lock()
        # parsed index.json memo: the boot path probes is_chunked + reads the
        # manifest on EVERY restore, which must not cost per-boot disk I/O +
        # JSON parse once the snapshot is warm. Invalidated on save/evict
        # (indexes are immutable between those, and callers never mutate the
        # returned dict).
        self._index_cache: Dict[str, Dict[str, Any]] = {}

    def _dir(self, name: str) -> Path:
        return self.root / name

    def has(self, name: str) -> bool:
        return (self._dir(name) / "index.json").exists()

    def is_chunked(self, name: str) -> bool:
        """True when this snapshot is stored in the v2 chunk-manifest format."""
        return self.has(name) and self.read_index(name).get("format") == 2

    # ------------------------------------------------------------------- save
    def save(self, name: str, params,
             first_use_order: List[str] | None = None) -> int:
        """Write a snapshot atomically; returns total stored bytes.

        With a blob store attached this writes the v2 format: each leaf's raw
        bytes split into fixed-size content-addressed chunks (stored once per
        unique content across ALL snapshots), and an index.json that is pure
        metadata — the chunk manifest a delta restore diffs against a host's
        chunk tier.

        ``first_use_order`` (leaf paths in execution first-touch order, from
        deploy-time profiling) is persisted into the index so restores can
        stream leaves in the order execution will need them (manifest v2.1;
        purely advisory — readers without it fall back to ordinal order).
        """
        if self.blobs is not None:
            return self._save_v2(name, params, first_use_order=first_use_order)
        items, treedef = _flatten_with_paths(params)
        d = self._dir(name)
        tmp = d.with_name(d.name + ".tmp")
        shutil.rmtree(tmp, ignore_errors=True)
        tmp.mkdir(parents=True)
        index: Dict[str, Any] = {"leaves": [], "treedef": None}
        if first_use_order:
            index["first_use_order"] = list(first_use_order)
        total = 0
        for i, (path, leaf) in enumerate(items):
            arr = np.asarray(leaf)
            stored, logical = _to_storable(arr)
            fname = f"leaf_{i:05d}.npy"
            np.save(tmp / fname, stored, allow_pickle=False)
            total += (tmp / fname).stat().st_size
            index["leaves"].append({
                "path": path, "file": fname,
                "shape": list(arr.shape), "dtype": logical,
            })
        # round-trip the treedef through an example tree of leaf ordinals
        example = jax.tree.unflatten(treedef, list(range(len(items))))
        index["treedef"] = _encode_structure(example)
        (tmp / "index.json").write_text(json.dumps(index))
        shutil.rmtree(d, ignore_errors=True)
        os.replace(tmp, d)                                   # atomic publish
        with self._lock:
            self._index_cache[name] = index
        return total

    def _save_v2(self, name: str, params,
                 first_use_order: List[str] | None = None) -> int:
        from repro.core.blobstore import split_chunks
        items, treedef = _flatten_with_paths(params)
        chunk_bytes = self.blobs.chunk_bytes
        index: Dict[str, Any] = {"format": 2, "chunk_bytes": chunk_bytes,
                                 "leaves": [], "treedef": None}
        if first_use_order:
            index["version"] = "2.1"
            index["first_use_order"] = list(first_use_order)
        raws: List[Tuple[str, Any, str, str, bytes]] = []
        for path, leaf in items:
            arr = np.asarray(leaf)
            stored, logical = _to_storable(arr)
            raws.append((path, list(arr.shape), logical, str(stored.dtype),
                         np.ascontiguousarray(stored).tobytes()))
        # put_all writes chunks AND takes the snapshot reference atomically
        # inside the ChunkStore's own lock — a concurrent evict can never
        # delete a dedup-hit chunk between its put and its ref. Deliberately
        # OUTSIDE this store's lock: read_index (on every boot's restore
        # path) must not stall behind a multi-second snapshot write.
        leaf_cid_lists = self.blobs.put_all(
            [split_chunks(raw, chunk_bytes) for *_meta, raw in raws])
        total = 0
        for (path, shape, logical, stored_dtype, raw), leaf_cids \
                in zip(raws, leaf_cid_lists):
            total += len(raw)
            index["leaves"].append({
                "path": path, "chunks": leaf_cids, "nbytes": len(raw),
                "shape": shape, "dtype": logical,
                "stored_dtype": stored_dtype,
            })
        example = jax.tree.unflatten(treedef, list(range(len(items))))
        index["treedef"] = _encode_structure(example)
        with self._lock:
            old_cids: List[str] = []
            if self.has(name):                   # overwrite: release old chunks
                old = self._read_index_locked(name)
                if old.get("format") == 2:
                    old_cids = [c for e in old["leaves"] for c in e["chunks"]]
            d = self._dir(name)
            tmp = d.with_name(d.name + ".tmp")
            shutil.rmtree(tmp, ignore_errors=True)
            tmp.mkdir(parents=True)
            (tmp / "index.json").write_text(json.dumps(index))
            shutil.rmtree(d, ignore_errors=True)
            os.replace(tmp, d)                               # atomic publish
            self._index_cache[name] = index
            if old_cids:
                self.blobs.decref(old_cids)
        return total

    # ------------------------------------------------------------------- load
    def read_index(self, name: str) -> Dict[str, Any]:
        """Parse index.json (tree structure + per-leaf shape/dtype and either
        a file name (v1) or a chunk manifest (v2)); memoized until the
        snapshot is overwritten or evicted."""
        with self._lock:
            return self._read_index_locked(name)

    def _read_index_locked(self, name: str) -> Dict[str, Any]:
        index = self._index_cache.get(name)
        if index is None:
            index = json.loads((self._dir(name) / "index.json").read_text())
            self._index_cache[name] = index
        return index

    @staticmethod
    def index_nbytes(index: Dict[str, Any]) -> int:
        """Logical stored bytes of a v2 index (sum of leaf byte lengths)."""
        return sum(int(e["nbytes"]) for e in index["leaves"])

    def chunk_ids(self, name: str) -> List[str]:
        """Every chunk id of a v2 snapshot, in manifest order (with repeats)."""
        return [c for e in self.read_index(name)["leaves"] for c in e["chunks"]]

    @staticmethod
    def leaf_order(index: Dict[str, Any]) -> List[int]:
        """Leaf ordinals in restore order: the manifest's ``first_use_order``
        where present (paths the manifest doesn't know are skipped; leaves the
        order doesn't cover are appended in ordinal order), else identity.
        Always a permutation of ``range(len(leaves))``."""
        order = index.get("first_use_order")
        n = len(index["leaves"])
        if not order:
            return list(range(n))
        by_path = {e["path"]: i for i, e in enumerate(index["leaves"])}
        out = [by_path[p] for p in order if p in by_path]
        covered = set(out)
        out.extend(i for i in range(n) if i not in covered)
        return out

    @staticmethod
    def _leaf_from_chunks(entry: Dict[str, Any],
                          chunk_bytes: Callable[[str], bytes]) -> np.ndarray:
        raw = b"".join(chunk_bytes(cid) for cid in entry["chunks"])
        stored = np.frombuffer(raw, dtype=np.dtype(entry["stored_dtype"]))
        return _from_storable(stored, entry["dtype"]).reshape(entry["shape"])

    def assemble_tree(self, index: Dict[str, Any],
                      chunk_bytes: Callable[[str], bytes],
                      order: List[int] | None = None) -> Any:
        """Rebuild the host tree of a v2 index from a chunk-byte source —
        the delta restore's final step (``chunk_bytes`` may serve any mix of
        tier-resident, peer-fetched, and store-fetched chunks). ``order``
        (leaf ordinals, e.g. ``leaf_order(index)``) controls FETCH order only;
        leaves land at their ordinal position either way."""
        entries = index["leaves"]
        if order is None:
            order = self.leaf_order(index)
        leaves: List[Any] = [None] * len(entries)
        for i in order:
            leaves[i] = self._leaf_from_chunks(entries[i], chunk_bytes)
        return _rebuild_structure(index["treedef"], leaves)

    def iter_restore(self, name: str, mmap: bool = True):
        """Yield ``(ordinal, path, host_leaf)`` in first-use order, both
        formats — the streamed restore's producer. v2 assembles each leaf
        from the global chunk store as it's reached; v1 opens one .npy at a
        time (mmap'd by default). Unlike ``iter_host_leaves`` the iteration
        order follows the manifest's ``first_use_order`` when present."""
        d = self._dir(name)
        index = self.read_index(name)
        entries = index["leaves"]
        chunked = index.get("format") == 2
        for i in self.leaf_order(index):
            e = entries[i]
            if chunked:
                leaf = self._leaf_from_chunks(e, self.blobs.get)
            else:
                leaf = _from_storable(
                    np.load(d / e["file"], mmap_mode="r" if mmap else None),
                    e["dtype"])
            yield i, e["path"], leaf

    def iter_host_leaves(self, name: str, mmap: bool = True):
        """Yield host leaves one at a time, in ordinal order.

        The chunked-load primitive: a streaming caller can consume leaf k
        while leaf k+1 is still being opened, instead of waiting for the whole
        tree (``load_host`` itself is this iterator, fully drained; with mmap
        the v1 bytes page in lazily during the eventual device transfer —
        v2 leaves are assembled from chunks, so ``mmap`` is a no-op there).
        """
        d = self._dir(name)
        index = self.read_index(name)
        if index.get("format") == 2:
            for e in index["leaves"]:
                yield self._leaf_from_chunks(e, self.blobs.get)
            return
        for e in index["leaves"]:
            yield _from_storable(
                np.load(d / e["file"], mmap_mode="r" if mmap else None),
                e["dtype"])

    def load_host(self, name: str, mmap: bool = True) -> Any:
        """Load as host numpy arrays (v1: mmap'd by default; v2: assembled
        from the global chunk store). No device transfer."""
        index = self.read_index(name)
        leaves = list(self.iter_host_leaves(name, mmap=mmap))
        return _rebuild_structure(index["treedef"], leaves)

    def load_host_async(self, name: str, mmap: bool = True):
        """Kick off ``load_host`` on a background thread; returns a Future."""
        from repro.core.boot import spawn_future
        return spawn_future(lambda: self.load_host(name, mmap=mmap),
                            name=f"snapshot-load-{name[:12]}")

    def load_to_device(self, name: str, shardings=None, mmap: bool = True) -> Any:
        """mmap -> device_put (optionally with target shardings)."""
        host = self.load_host(name, mmap=mmap)
        if shardings is None:
            return jax.tree.map(jax.device_put, host)
        return jax.tree.map(jax.device_put, host, shardings)

    def nbytes(self, name: str) -> int:
        if self.has(name):
            index = self.read_index(name)
            if index.get("format") == 2:
                return self.index_nbytes(index)
        d = self._dir(name)
        return sum(f.stat().st_size for f in d.glob("leaf_*.npy"))

    def evict(self, name: str) -> None:
        """Remove a snapshot; v2 releases its chunk references (shared chunks
        survive as long as any other snapshot still references them)."""
        with self._lock:
            if self.blobs is not None and self.has(name):
                index = self._read_index_locked(name)
                if index.get("format") == 2:
                    self.blobs.decref(
                        c for e in index["leaves"] for c in e["chunks"])
            self._index_cache.pop(name, None)
            shutil.rmtree(self._dir(name), ignore_errors=True)

    def names(self):
        return sorted(p.name for p in self.root.iterdir()
                      if p.is_dir() and not p.name.endswith(".tmp"))


def tree_host_nbytes(tree) -> int:
    """Total bytes of a host-leaf tree — the snapshot tier's accounting unit
    (repro.core.scheduler byte-bounds its per-host RAM caches with this)."""
    return int(sum(getattr(leaf, "nbytes", 0) for leaf in jax.tree.leaves(tree)))


# --------------------------------------------------------------- generic ckpt

def save_generic_checkpoint(path: str | Path, params) -> int:
    """The 'interpreted-language' comparison path: one pickle-style npz, fp32,
    no layout guarantees — loading requires full parse + cast (no mmap)."""
    items, _ = _flatten_with_paths(params)
    arrays = {f"a{i}": np.asarray(leaf, dtype=np.float32) for i, (p, leaf) in enumerate(items)}
    np.savez(path, **arrays)
    return Path(str(path) if str(path).endswith(".npz") else str(path) + ".npz").stat().st_size


def load_generic_host(path: str | Path, like) -> Any:
    """Host half of the generic load: full parse + cast, no device transfer.

    Split out so the boot pipeline can time it as its own stage (and overlap
    it with program acquisition) before the streamed device_put.
    """
    with np.load(path) as z:
        arrays = [z[f"a{i}"] for i in range(len(z.files))]
    leaves, treedef = jax.tree.flatten(like)
    cast = [np.asarray(a, dtype=l.dtype) for a, l in zip(arrays, leaves)]
    return jax.tree.unflatten(treedef, cast)


def load_generic_checkpoint(path: str | Path, like) -> Any:
    """Load + cast back to the target dtypes (pays the transform in the start path)."""
    host = load_generic_host(path, like)
    return jax.tree.map(jax.device_put, host)


# --------------------------------------------- structure (de)serialization

def _encode_structure(obj):
    """Encode a pytree whose leaves are ints (ordinals) into JSON."""
    if isinstance(obj, dict):
        return {"__kind__": "dict", "items": {k: _encode_structure(v) for k, v in obj.items()}}
    if isinstance(obj, (list, tuple)):
        return {"__kind__": type(obj).__name__,
                "items": [_encode_structure(v) for v in obj]}
    if isinstance(obj, int):
        return {"__kind__": "leaf", "ordinal": obj}
    if obj is None:
        return {"__kind__": "none"}
    raise TypeError(f"unsupported structure node: {type(obj)}")


def _rebuild_structure(enc, leaves):
    kind = enc["__kind__"]
    if kind == "dict":
        return {k: _rebuild_structure(v, leaves) for k, v in enc["items"].items()}
    if kind == "list":
        return [_rebuild_structure(v, leaves) for v in enc["items"]]
    if kind == "tuple":
        return tuple(_rebuild_structure(v, leaves) for v in enc["items"])
    if kind == "leaf":
        return leaves[enc["ordinal"]]
    if kind == "none":
        return None
    raise TypeError(kind)
