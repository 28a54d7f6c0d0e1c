"""Elastic topology-resharding checkpoint restore.

A sharded checkpoint (``train/checkpoint.py``) is N per-process shard
files, each holding the slices *that* process's devices owned plus a
``shard-<pidx>.subshards.json`` sidecar recording every slice's offset
into its global array.  Taken together, the sidecars describe the FULL
global layout of every leaf — which means the checkpoint is not tied to
the process count that wrote it: any reader that knows which regions of
each global array it needs can work out exactly which stored slices
overlap those regions and read only those npz members.

This module is that reader.  :class:`CheckpointLayout` scans a step
directory into a per-leaf catalogue of ``(process, npz key, start,
shape)`` parts; :meth:`CheckpointLayout.read_region` reassembles an
arbitrary region of one leaf from the overlapping parts (verifying the
parts cover it exactly — disjointly and completely); and
:func:`restore_resharded` drives that per leaf of a state template,
taking the target regions from a ``ParallelPlan``-derived sharding tree
(``StepRunner.state_shardings``) so an N-process checkpoint restores
onto M processes under any target plan — ddp, fsdp ZeRO-3, demoted or
engaged pp — with each target process touching only the byte ranges
that overlap its new shards.

Read granularity is the stored sub-shard: npz members are zip-stored
(uncompressed), so loading one member is a contiguous file read of just
that slice, and members whose recorded extent misses the target region
are never opened.

Restores are value-exact: parts are written by ``save_sharded`` from
host snapshots, and reassembly is pure placement (no arithmetic), so a
restore onto ANY topology yields bit-identical params and optimizer
moments.  The loss *trajectory* after restore is additionally
bit-identical whenever the target mesh has the same total device count
(same SPMD program, same reduction order); across different device
counts the trajectory matches to reduction-order tolerance.
"""
from __future__ import annotations

import json
import math
import os
import re
import zipfile
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from repro.train import checkpoint as ckpt

__all__ = ["CheckpointLayout", "Part", "restore_resharded",
           "target_regions"]

Region = Tuple[slice, ...]


@dataclass(frozen=True)
class Part:
    """One stored slice of one leaf: process ``pidx``'s npz member
    ``npz_key`` holds ``global[start : start+shape]``."""

    pidx: int
    npz_key: str
    start: Tuple[int, ...]
    shape: Tuple[int, ...]

    @property
    def stop(self) -> Tuple[int, ...]:
        return tuple(s + n for s, n in zip(self.start, self.shape))


def _volume(shape) -> int:
    return int(math.prod(shape))


def _normalize(region: Optional[Region], shape: Tuple[int, ...]) -> Region:
    """Index tuple -> concrete (start, stop) slices, one per dim."""
    if region is None:
        return tuple(slice(0, n) for n in shape)
    region = tuple(region)
    if len(region) != len(shape):
        raise ValueError(f"region rank {len(region)} != leaf rank "
                         f"{len(shape)}")
    out = []
    for sl, n in zip(region, shape):
        start, stop, stride = sl.indices(n)
        if stride != 1:
            raise ValueError("strided regions are not checkpoint shards")
        out.append(slice(start, stop))
    return tuple(out)


def _intersect(part: Part, region: Region) -> Optional[Region]:
    """Global-coordinate intersection, or None when empty."""
    inter = []
    for sl, p0, p1 in zip(region, part.start, part.stop):
        lo, hi = max(sl.start, p0), min(sl.stop, p1)
        if lo >= hi:
            return None
        inter.append(slice(lo, hi))
    return tuple(inter)


class CheckpointLayout:
    """The global layout of one committed sharded checkpoint, scanned
    from its manifest + per-shard sidecars + npz directories (zip
    central directories only — no array data is read at scan time)."""

    def __init__(self, base_dir: str, step: int, manifest: Dict[str, Any]):
        self.base_dir = base_dir
        self.step = step
        self.manifest = manifest
        self.process_count = int(manifest["process_count"])
        #: leaf key -> global shape (sub-sharded leaves only)
        self.shapes: Dict[str, Tuple[int, ...]] = {}
        #: leaf key -> stored parts (sub-sharded leaves only)
        self.parts: Dict[str, List[Part]] = {}
        #: leaf key -> process indices whose shard holds it whole
        self.full: Dict[str, List[int]] = {}
        self._npz: Dict[int, Any] = {}

    # -- scan --------------------------------------------------------------

    @classmethod
    def scan(cls, base_dir: str, step: Optional[int] = None
             ) -> "CheckpointLayout":
        if step is None:
            step = ckpt.latest_step(base_dir)
            if step is None:
                raise FileNotFoundError(
                    f"no complete sharded checkpoint under {base_dir}")
        d = ckpt.step_dir(base_dir, step)
        with open(os.path.join(d, "manifest.json")) as f:
            manifest = json.load(f)
        self = cls(base_dir, step, manifest)
        for pidx in range(self.process_count):
            shard = self._shard_path(pidx)
            if not os.path.exists(shard):
                raise FileNotFoundError(
                    f"checkpoint step {step} manifest names "
                    f"{self.process_count} shards but {shard} is missing")
            subs: Dict[str, Any] = {}
            sj = re.sub(r"\.npz$", ".subshards.json", shard)
            if os.path.exists(sj):
                with open(sj) as f:
                    subs = json.load(f)
            for key, rec in subs.items():
                self.shapes[key] = tuple(rec["global_shape"])
                plist = self.parts.setdefault(key, [])
                for k, p in enumerate(rec["parts"]):
                    plist.append(Part(pidx, f"{key}@sub{k}",
                                      tuple(p["start"]), tuple(p["shape"])))
            with zipfile.ZipFile(shard) as z:
                for name in z.namelist():
                    if not name.endswith(".npy") or "@sub" in name:
                        continue
                    self.full.setdefault(name[:-4], []).append(pidx)
        return self

    def _shard_path(self, pidx: int) -> str:
        return os.path.join(ckpt.step_dir(self.base_dir, self.step),
                            ckpt._shard_name(pidx))

    # -- reads -------------------------------------------------------------

    def _member(self, pidx: int, npz_key: str) -> np.ndarray:
        npz = self._npz.get(pidx)
        if npz is None:
            npz = self._npz[pidx] = np.load(self._shard_path(pidx))
        return npz[npz_key]

    def keys(self) -> List[str]:
        return sorted(set(self.full) | set(self.parts))

    def covering_parts(self, key: str, region: Region) -> List[Part]:
        """The stored parts whose extent intersects ``region``, one per
        distinct ``(start, shape)`` (replicas across processes collapse
        to the lowest process index — any copy is value-identical)."""
        seen = set()
        out = []
        for part in self.parts.get(key, ()):
            span = (part.start, part.shape)
            if span in seen or _intersect(part, region) is None:
                continue
            seen.add(span)
            out.append(part)
        return out

    def read_region(self, key: str, region: Optional[Region] = None
                    ) -> np.ndarray:
        """Reassemble ``global[region]`` of leaf ``key`` from exactly
        the stored parts that overlap it.  Raises when the parts do not
        tile the region (a gap means the checkpoint never stored those
        elements; an overlap of distinct parts means a corrupt layout)."""
        if key in self.full:
            pidx = self.full[key][0]
            arr = self._member(pidx, key)
            if region is None:
                return arr
            return arr[_normalize(region, arr.shape)]
        if key not in self.parts:
            raise KeyError(f"leaf {key!r} not in checkpoint "
                           f"step {self.step}")
        shape = self.shapes[key]
        region = _normalize(region, shape)
        parts = self.covering_parts(key, region)
        if not parts:
            raise ValueError(f"no stored parts of {key!r} overlap "
                             f"region {region}")
        out = np.zeros(tuple(sl.stop - sl.start for sl in region),
                       dtype=self._member(parts[0].pidx,
                                          parts[0].npz_key).dtype)
        inters = []
        covered = 0
        for part in parts:
            inter = _intersect(part, region)
            dst = tuple(slice(sl.start - r.start, sl.stop - r.start)
                        for sl, r in zip(inter, region))
            src = tuple(slice(sl.start - p0, sl.stop - p0)
                        for sl, p0 in zip(inter, part.start))
            out[dst] = self._member(part.pidx, part.npz_key)[src]
            covered += _volume(sl.stop - sl.start for sl in inter)
            inters.append(inter)
        # exact-tiling proof: pairwise-disjoint intersections whose
        # volumes sum to the region volume cover it exactly
        for i in range(len(inters)):
            for j in range(i + 1, len(inters)):
                if _intersect(Part(0, "", tuple(sl.start for sl in inters[i]),
                                   tuple(sl.stop - sl.start
                                         for sl in inters[i])),
                              inters[j]) is not None:
                    raise ValueError(
                        f"overlapping stored parts of {key!r}: "
                        f"{inters[i]} and {inters[j]}")
        want = _volume(sl.stop - sl.start for sl in region)
        if covered != want:
            raise ValueError(
                f"stored parts of {key!r} cover {covered} of {want} "
                f"elements in region {region} — the source layout has a "
                f"gap (lost shard?)")
        return out

    def pipeline_state(self) -> Optional[Dict[str, Any]]:
        """The lowest-index shard's pipeline sidecar (the restoring side
        re-aims it elastically: ``DataPipeline.restore(.., elastic=True)``
        keys only on the global position, not the writer's host layout)."""
        for pidx in range(self.process_count):
            pj = re.sub(r"\.npz$", ".pipeline.json", self._shard_path(pidx))
            if os.path.exists(pj):
                with open(pj) as f:
                    return json.load(f)
        return None

    def close(self) -> None:
        for npz in self._npz.values():
            npz.close()
        self._npz.clear()

    def __enter__(self) -> "CheckpointLayout":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False


def target_regions(sharding, global_shape: Tuple[int, ...]) -> List[Region]:
    """The distinct regions of a ``global_shape`` array that THIS
    process's devices own under ``sharding`` (replicated device copies
    collapse to one region).  These are exactly the byte ranges a
    resharding restore must read."""
    global_shape = tuple(global_shape)
    imap = sharding.addressable_devices_indices_map(global_shape)
    regions: List[Region] = []
    seen = set()
    for idx in imap.values():
        reg = _normalize(idx, global_shape)
        span = tuple((sl.start, sl.stop) for sl in reg)
        if span in seen:
            continue
        seen.add(span)
        regions.append(reg)
    return regions


def restore_resharded(base_dir: str, like, *, step: Optional[int] = None,
                      shardings=None
                      ) -> Tuple[Any, Optional[Dict[str, Any]],
                                 Dict[str, Any]]:
    """Restore a sharded checkpoint written by ANY number of processes
    into the structure of ``like`` on THIS process, reading only the
    stored slices that overlap this process's target shards.

    ``shardings`` is a pytree of ``NamedSharding`` congruent with
    ``like`` (``StepRunner.state_shardings`` — i.e. the target
    ``ParallelPlan`` made concrete); when None, every leaf is read whole
    (single-host reassembly).  Leaves the writer stored whole (it had
    the full value on one process) are read whole from one shard —
    granularity can't be finer than what was stored.

    Returns ``(tree, pipeline_state_dict, manifest)`` with host numpy
    leaves in ``like``'s dtypes; regions outside this process's shards
    stay zero and are never read by ``place_state``/``device_put``.
    Mirrors :func:`repro.train.checkpoint.restore_sharded`'s contract,
    minus the same-topology requirement.
    """
    import jax

    with CheckpointLayout.scan(base_dir, step=step) as layout:
        flat_like, treedef = jax.tree_util.tree_flatten_with_path(like)
        if shardings is not None:
            sh_leaves = jax.tree_util.tree_leaves(shardings)
            if len(sh_leaves) != len(flat_like):
                raise ValueError(
                    f"shardings tree has {len(sh_leaves)} leaves, "
                    f"state template has {len(flat_like)}")
        else:
            sh_leaves = [None] * len(flat_like)
        leaves = []
        for (path, leaf), sh in zip(flat_like, sh_leaves):
            key = ckpt.leaf_key(path)
            shape = tuple(leaf.shape)
            stored = layout.shapes.get(key)
            if stored is not None and stored != shape:
                raise ValueError(
                    f"checkpoint leaf {key!r} has global shape {stored}, "
                    f"template wants {shape}")
            if key in layout.full or sh is None:
                arr = layout.read_region(key)
                if arr.shape != shape:
                    raise ValueError(f"checkpoint leaf {key!r} has shape "
                                     f"{arr.shape}, template wants {shape}")
            else:
                # fill exactly this process's regions; dtype follows the
                # stored parts, buffer allocated on the first block
                arr = None
                for reg in target_regions(sh, shape):
                    block = layout.read_region(key, reg)
                    if arr is None:
                        arr = np.zeros(shape, dtype=block.dtype)
                    arr[reg] = block
                if arr is None:  # a process with no shard of this leaf
                    arr = np.zeros(shape, dtype=np.float32)
            leaves.append(arr.astype(np.dtype(leaf.dtype))
                          if hasattr(leaf, "dtype") and
                          np.dtype(arr.dtype) != np.dtype(leaf.dtype)
                          else arr)
        tree = jax.tree_util.tree_unflatten(treedef, leaves)
        return tree, layout.pipeline_state(), dict(layout.manifest)
