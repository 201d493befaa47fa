"""Sharded checkpointing with torrent-style restore.

Layout:
  <root>/step_<n>/manifest.json       tree structure, shapes, dtypes, pieces
  <root>/step_<n>/piece_<i>.npz       flat-chunked payload pieces
  <root>/step_<n>/COMMITTED           write barrier marker

Pieces (not per-tensor files) are the unit of both I/O and swarm exchange:
on restore in a multi-pod job only the seeder pod reads from the store;
every other pod receives pieces over the interconnect via
parallel/weight_torrent (ppermute ring) or host-side via core/swarm's
rarest-first plan.  `async_save` runs serialisation off-thread so the train
loop never blocks (the step's arrays are snapshotted to host first).

Every committed step also carries `swarm.json`: a `PieceManifest` (the
torrent metainfo) over the step's canonical *image* — manifest.json plus
the piece files packed into one byte stream by `pack_step_image` — so a
checkpoint can be advertised to the volunteer swarm as a regular
piece-wise Application and serving replicas can cold-start from peers
(`checkpoint/swarm_restore.py`) instead of hammering this store.
"""
from __future__ import annotations

import json
import os
import shutil
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.core.workunit import PieceManifest

# canonical step-image framing: magic + json file table + file bytes
IMAGE_MAGIC = b"CKPTIMG1\n"


def _image_files(d: str) -> List[str]:
    """Canonical file order for a step's swarm image: the tree manifest
    first, then the payload pieces (COMMITTED and swarm.json are framing,
    not content, and stay out of the image)."""
    pieces = sorted(fn for fn in os.listdir(d)
                    if fn.startswith("piece_") and fn.endswith(".npz"))
    return ["manifest.json"] + pieces


def pack_step_image(d: str) -> bytearray:
    """Pack a committed step directory into the canonical image bytes the
    swarm manifest hashes: magic, a json file table, then the files'
    bytes concatenated in table order.  The files are read straight into
    one preallocated buffer, so packing holds one copy of the step."""
    files = _image_files(d)
    sizes = [os.path.getsize(os.path.join(d, fn)) for fn in files]
    table = [{"name": fn, "size": n} for fn, n in zip(files, sizes)]
    header = IMAGE_MAGIC + json.dumps({"files": table},
                                      sort_keys=True).encode() + b"\n"
    image = bytearray(len(header) + sum(sizes))
    image[:len(header)] = header
    view = memoryview(image)
    ofs = len(header)
    for fn, n in zip(files, sizes):
        with open(os.path.join(d, fn), "rb") as f:
            if f.readinto(view[ofs:ofs + n]) != n:
                raise IOError(f"{fn} changed size while being packed")
        ofs += n
    return image


def unpack_step_image(image, dest_dir: str) -> List[str]:
    """Inverse of `pack_step_image`: write the step's files into
    `dest_dir` (plus a fresh COMMITTED marker) and return the file names.
    Callers verify the image against its PieceManifest *before* calling
    this — the framing here is trusted only after the content re-hash."""
    mv = memoryview(image)
    if bytes(mv[:len(IMAGE_MAGIC)]) != IMAGE_MAGIC:
        raise ValueError("not a checkpoint step image (bad magic)")
    ofs = len(IMAGE_MAGIC)
    end = ofs
    while end < len(mv) and mv[end] != 0x0A:        # newline-terminated
        end += 1
    header = json.loads(bytes(mv[ofs:end]).decode())
    ofs = end + 1
    os.makedirs(dest_dir, exist_ok=True)
    names = []
    for ent in header["files"]:
        n = int(ent["size"])
        with open(os.path.join(dest_dir, ent["name"]), "wb") as f:
            f.write(mv[ofs:ofs + n])
        ofs += n
        names.append(ent["name"])
    if ofs != len(mv):
        raise ValueError("trailing bytes after the declared file table")
    with open(os.path.join(dest_dir, "COMMITTED"), "w") as f:
        f.write(str(time.time()))
    return names


def _flatten_with_paths(tree) -> List[Tuple[str, Any]]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        out.append((key, leaf))
    return out


class CheckpointStore:
    def __init__(self, root: str, piece_bytes: int = 64 << 20,
                 keep_last: int = 3, swarm_piece_bytes: int = 4 << 20):
        self.root = root
        self.piece_bytes = piece_bytes
        self.keep_last = keep_last
        # granularity of the *swarm* manifest over the packed step image;
        # smaller than the I/O piece size so a flash crowd of replicas
        # disperses across many holders instead of queueing on whole shards
        self.swarm_piece_bytes = swarm_piece_bytes
        os.makedirs(root, exist_ok=True)

    def step_dir(self, step: int) -> str:
        return os.path.join(self.root, f"step_{step:08d}")

    # ------------------------------------------------------------------ #
    def save(self, step: int, tree, extra: Optional[dict] = None) -> str:
        d = os.path.join(self.root, f"step_{step:08d}")
        tmp = d + ".tmp"
        os.makedirs(tmp, exist_ok=True)
        entries = _flatten_with_paths(tree)
        manifest = {"step": step, "extra": extra or {}, "leaves": [],
                    "pieces": []}
        # pack leaves into pieces
        piece, piece_sz, piece_idx = {}, 0, 0
        for key, leaf in entries:
            arr = np.asarray(leaf)
            manifest["leaves"].append({
                "key": key, "shape": list(arr.shape), "dtype": str(arr.dtype),
                "piece": piece_idx, "name": f"a{len(piece)}"})
            piece[f"a{len(piece)}"] = arr
            piece_sz += arr.nbytes
            if piece_sz >= self.piece_bytes:
                np.savez(os.path.join(tmp, f"piece_{piece_idx:05d}.npz"),
                         **piece)
                manifest["pieces"].append(piece_idx)
                piece, piece_sz = {}, 0
                piece_idx += 1
        if piece:
            np.savez(os.path.join(tmp, f"piece_{piece_idx:05d}.npz"), **piece)
            manifest["pieces"].append(piece_idx)
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
        # emit the swarm metainfo: a PieceManifest (content-hashed, like a
        # .torrent) over the step's canonical packed image, so replicas
        # can join the distribution swarm straight off the step directory.
        # Successive committed steps form a revision chain (version +
        # prev_manifest_hash): a replica holding v(k) seeds its v(k+1)
        # inventory from the pieces the delta left unchanged.
        prev_pm = None
        prior = [s for s in self.steps() if s < step]
        if prior:
            try:
                prev_pm = self.swarm_manifest(prior[-1])
            except Exception:
                prev_pm = None
        pm = PieceManifest.from_bytes(
            self.swarm_app_id(step), pack_step_image(tmp),
            self.swarm_piece_bytes,
            version=(prev_pm.version + 1 if prev_pm is not None else 1),
            prev=prev_pm)
        with open(os.path.join(tmp, "swarm.json"), "w") as f:
            json.dump({"app_id": pm.app_id, "piece_bytes": pm.piece_bytes,
                       "total_bytes": pm.total_bytes,
                       "piece_hashes": list(pm.piece_hashes),
                       "version": pm.version,
                       "prev_manifest_hash": pm.prev_manifest_hash,
                       "manifest_hash": pm.manifest_hash}, f)
        with open(os.path.join(tmp, "COMMITTED"), "w") as f:
            f.write(str(time.time()))
        if os.path.isdir(d):
            shutil.rmtree(d)
        os.rename(tmp, d)
        self._gc()
        return d

    # ------------------------------------------------------------------ #
    def swarm_app_id(self, step: int) -> str:
        """The Application id a step is advertised under in the swarm."""
        return f"ckpt-{os.path.basename(os.path.normpath(self.root))}" \
               f"-step{step:08d}"

    def pack_image(self, step: Optional[int] = None) -> bytearray:
        """The committed step's canonical swarm image bytes."""
        step = step if step is not None else self.latest_step()
        assert step is not None, "no committed checkpoint found"
        return pack_step_image(self.step_dir(step))

    def swarm_manifest(self, step: Optional[int] = None) -> PieceManifest:
        """The PieceManifest `save` emitted for a committed step
        (rebuilt from the files for pre-swarm.json step dirs)."""
        step = step if step is not None else self.latest_step()
        assert step is not None, "no committed checkpoint found"
        path = os.path.join(self.step_dir(step), "swarm.json")
        if not os.path.exists(path):
            return PieceManifest.from_bytes(self.swarm_app_id(step),
                                            self.pack_image(step),
                                            self.swarm_piece_bytes)
        with open(path) as f:
            doc = json.load(f)
        pm = PieceManifest(doc["app_id"], int(doc["piece_bytes"]),
                           int(doc["total_bytes"]),
                           tuple(doc["piece_hashes"]), content_hashed=True,
                           version=int(doc.get("version", 1)),
                           prev_manifest_hash=doc.get("prev_manifest_hash"))
        assert pm.manifest_hash == doc["manifest_hash"], \
            "swarm.json does not match its own metainfo"
        return pm

    def _gc(self) -> None:
        steps = self.steps()
        for s in steps[:-self.keep_last]:
            shutil.rmtree(os.path.join(self.root, f"step_{s:08d}"),
                          ignore_errors=True)

    def steps(self) -> List[int]:
        out = []
        for fn in sorted(os.listdir(self.root)):
            d = os.path.join(self.root, fn)
            if fn.startswith("step_") and \
                    os.path.exists(os.path.join(d, "COMMITTED")):
                out.append(int(fn[5:]))
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        s = self.steps()
        return s[-1] if s else None

    # ------------------------------------------------------------------ #
    def restore(self, template, step: Optional[int] = None
                ) -> Tuple[Any, dict]:
        """Restore into the structure of `template` (pytree of arrays or
        ShapeDtypeStructs)."""
        step = step if step is not None else self.latest_step()
        assert step is not None, "no committed checkpoint found"
        d = os.path.join(self.root, f"step_{step:08d}")
        with open(os.path.join(d, "manifest.json")) as f:
            manifest = json.load(f)
        pieces: Dict[int, Any] = {}
        values: Dict[str, np.ndarray] = {}
        for leaf in manifest["leaves"]:
            pid = leaf["piece"]
            if pid not in pieces:
                pieces[pid] = np.load(
                    os.path.join(d, f"piece_{pid:05d}.npz"))
            values[leaf["key"]] = pieces[pid][leaf["name"]]
        flat, treedef = jax.tree_util.tree_flatten_with_path(template)
        out = []
        for path, leaf in flat:
            key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                           for p in path)
            arr = values[key]
            want = getattr(leaf, "dtype", None)
            if want is not None and str(arr.dtype) != str(want):
                arr = arr.astype(want)
            out.append(arr)
        tree = jax.tree_util.tree_unflatten(treedef, out)
        return tree, manifest["extra"]

    def restore_distributed(self, template, mesh, step: Optional[int] = None,
                            pod_axis: str = "pod"):
        """Torrent restore: seeder pod reads, pieces ride the ring.

        On the single-controller CPU stand-in this demonstrates the
        collective path (weight_torrent); a multi-controller deployment
        would gate the `restore()` call on pod rank.
        """
        tree, extra = self.restore(template, step)
        if mesh is not None and pod_axis in mesh.shape:
            from repro.parallel.weight_torrent import torrent_broadcast
            tree = torrent_broadcast(tree, mesh, axis=pod_axis)
        return tree, extra


def async_save(store: CheckpointStore, step: int, tree,
               extra: Optional[dict] = None) -> threading.Thread:
    """Snapshot to host, then serialise in a background thread."""
    host_tree = jax.tree_util.tree_map(lambda x: np.asarray(x), tree)
    th = threading.Thread(target=store.save, args=(step, host_tree, extra),
                          daemon=True)
    th.start()
    return th
