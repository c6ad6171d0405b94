"""Sharded, crash-safe checkpointing THROUGH CFS (the paper's technique as a
first-class framework feature).

Layout on the volume:
    /ckpt/step_<N>.tmp/...              (in-flight)
    /ckpt/step_<N>/<leaf-path>.shard<k> (tensor shards, large-file extents)
    /ckpt/step_<N>/MANIFEST             (small file — aggregated extent path)
    /ckpt/LATEST                        (small file, atomic commit pointer)

Crash safety: data files first, MANIFEST second, LATEST last — a crash at
any point leaves the previous checkpoint loadable (tested with injected
crashes).  Every shard file carries a CRC32 in the manifest; restore checks
it on the host, over the bytes read, before the leaf reaches ``put``.  The
CRC of each shard runs on a worker thread while the next shard is read.

Elasticity: tensors are split into ``shards`` along dim 0 where possible,
and a leaf is whatever its shards hold, joined along dim 0, so a
checkpoint written by H hosts loads on H' ≠ H (re-sharding happens at
device_put with the new mesh's shardings).

A shard file is ``RPT1``, the header's length, a JSON header padded with
spaces so that the rows start at a multiple of 64 bytes, then the rows.
Restore takes the rows as a view of the bytes read: every leaf goes to
``put`` shard by shard and is joined where ``put`` left its shards, so a
leaf put on the device is joined there, with no host copy of its rows.
"""

from __future__ import annotations

import json
import math
import zlib
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from .. import obs
from ..core.client import NotFound
from ..core.fs import CfsMount

__all__ = ["CheckpointManager", "InjectedCrash", "tensor_to_bytes"]

_MAGIC = b"RPT1"
_ROW_ALIGN = 64   # a shard's rows start at a multiple of this


class InjectedCrash(RuntimeError):
    """A crash injected on purpose (``crash_at`` / ``crash_after``).

    Launchers resume from the last checkpoint on this class only; any other
    error, a device fault among them, propagates."""


def tensor_to_bytes(arr: np.ndarray) -> bytes:
    header = json.dumps({"dtype": str(arr.dtype),
                         "shape": list(arr.shape)}).encode()
    header += b" " * (-(8 + len(header)) % _ROW_ALIGN)
    raw = np.ascontiguousarray(arr).tobytes()
    return (_MAGIC + len(header).to_bytes(4, "little") + header + raw)


def _parse_header(data: bytes) -> Tuple[np.dtype, Tuple[int, ...], int]:
    """The dtype and shape of a tensor file, and the offset of its rows."""
    if data[:4] != _MAGIC:
        raise IOError("bad tensor file")
    hlen = int.from_bytes(data[4:8], "little")
    header = json.loads(data[8 : 8 + hlen].decode())
    return np.dtype(header["dtype"]), tuple(header["shape"]), 8 + hlen


def _rows(data: bytes, dtype: np.dtype, shape: Tuple[int, ...],
          path: str) -> np.ndarray:
    """The rows of the tensor file ``data``: a read-only view of its bytes.
    The file must hold ``dtype``, the rank and trailing shape of ``shape``,
    and exactly its rows' bytes."""
    fdtype, fshape, offset = _parse_header(data)
    count = math.prod(fshape)
    if (fdtype != dtype or len(fshape) != len(shape)
            or fshape[1:] != shape[1:]
            or len(data) - offset != count * fdtype.itemsize):
        raise IOError(f"{path} does not fit its leaf")
    return np.frombuffer(data, fdtype, count, offset).reshape(fshape)


def _joined_shape(parts: List[np.ndarray]) -> Tuple[int, ...]:
    """The shape of ``parts`` joined along dim 0 (a lone part's own)."""
    if len(parts) == 1:
        return parts[0].shape
    return (sum(p.shape[0] for p in parts),) + parts[0].shape[1:]


def _leaf_name(path) -> str:
    return "~".join(
        str(p.key) if hasattr(p, "key") else str(p.idx) for p in path)


def _flatten(tree: Any) -> List[Tuple[str, np.ndarray]]:
    import jax
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [(_leaf_name(path), np.asarray(leaf)) for path, leaf in flat]


class CheckpointManager:
    def __init__(self, mount: CfsMount, base: str = "/ckpt",
                 shards: int = 1, keep_n: int = 2):
        self.mnt = mount
        self.base = base
        self.shards = shards
        self.keep_n = keep_n
        if not self.mnt.exists(base):
            self.mnt.mkdir(base)

    # ---- save ----------------------------------------------------------------
    def save(self, step: int, tree: Any,
             crash_after: Optional[int] = None) -> str:
        """Write checkpoint for ``step``.  ``crash_after``: fault injection —
        raise after N file writes (tests crash-safety)."""
        d = f"{self.base}/step_{step}"
        if self.mnt.exists(d):
            return d
        self.mnt.mkdir(d)
        manifest: Dict[str, Any] = {"step": step, "tensors": {}}
        writes = 0
        for name, arr in _flatten(tree):
            nsh = self.shards if (arr.ndim > 0 and arr.shape[0] >= self.shards
                                  and arr.shape[0] % self.shards == 0) else 1
            if nsh > 1:
                per = arr.shape[0] // nsh
                parts = [tensor_to_bytes(arr[i * per : (i + 1) * per])
                         for i in range(nsh)]
            else:
                parts = [tensor_to_bytes(arr)]
            entry = {"shards": [], "dtype": str(arr.dtype),
                     "shape": list(arr.shape)}
            for k, part in enumerate(parts):
                path = f"{d}/{name}.shard{k}"
                self.mnt.write_file(path, part)
                writes += 1
                if crash_after is not None and writes >= crash_after:
                    raise InjectedCrash(
                        "injected crash during checkpoint save")
                entry["shards"].append(
                    {"path": path, "bytes": len(part),
                     "crc32": zlib.crc32(part) & 0xFFFFFFFF})
            manifest["tensors"][name] = entry
        # data fully durable -> manifest -> commit pointer (atomic order)
        self.mnt.write_file(f"{d}/MANIFEST", json.dumps(manifest).encode())
        if crash_after is not None and writes + 1 >= crash_after:
            raise InjectedCrash("injected crash before LATEST commit")
        if self.mnt.exists(f"{self.base}/LATEST"):
            self.mnt.unlink(f"{self.base}/LATEST")
        self.mnt.write_file(f"{self.base}/LATEST", str(step).encode())
        self._gc(step)
        return d

    def _gc(self, newest: int) -> None:
        steps = sorted(self.list_steps())
        for s in steps[: max(0, len(steps) - self.keep_n)]:
            d = f"{self.base}/step_{s}"
            for name in self.mnt.readdir(d):
                self.mnt.unlink(f"{d}/{name}")
            self.mnt.rmdir(d)

    # ---- load -----------------------------------------------------------------
    def list_steps(self) -> List[int]:
        out = []
        for name in self.mnt.readdir(self.base):
            if name.startswith("step_") and \
                    self.mnt.exists(f"{self.base}/{name}/MANIFEST"):
                out.append(int(name.split("_")[1]))
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        try:
            return int(self.mnt.read_file(f"{self.base}/LATEST").decode())
        except (NotFound, ValueError):
            steps = self.list_steps()
            return steps[-1] if steps else None

    def restore(self, tree_like: Any, step: Optional[int] = None,
                put: Callable[[np.ndarray], Any] = lambda arr: arr
                ) -> Tuple[Any, int]:
        """Restore into the structure and dtypes of ``tree_like`` (arrays or
        ``ShapeDtypeStruct``s).  Shards are read in leaf order on the
        calling thread, and each shard's CRC32 is computed on one worker
        thread while the next shard is read.  A leaf is finished once the
        first shard of the next leaf has been read (or none is left): each
        of its shards' CRCs is checked and its header checked before any of
        them is handed to ``put`` (a device transfer, say).  So the host
        holds one leaf's shard bytes and one more shard at a time.

        Every leaf goes to ``put`` shard by shard, each shard's rows a view
        of the bytes read, and the results are joined along dim 0 in their
        own library (on the device for ``jax.Array``s).  The
        ``ckpt.restore`` span counts in ``host_copy_bytes`` the stored bytes
        of rows that went through a host copy: a view not aligned to its
        dtype, a cast, or a host ``put`` result joined or copied off the
        bytes.  Each ``ckpt.crc32`` span times the wait for one shard's
        CRC and counts ``ready``: 1 where it was done before it was asked
        for; the CRC's own work is the worker's ``ckpt.crc32_work``."""
        import jax
        with obs.span("ckpt.restore") as sp, \
                ThreadPoolExecutor(max_workers=1) as crc_worker:
            step = self.latest_step() if step is None else step
            if step is None:
                raise NotFound("no checkpoint")
            d = f"{self.base}/step_{step}"
            manifest = json.loads(
                self.mnt.read_file(f"{d}/MANIFEST").decode())
            flat, treedef = jax.tree_util.tree_flatten_with_path(tree_like)
            sp.add(host_copy_bytes=0)
            names = [_leaf_name(path) for path, _ in flat]
            leaves: List[Any] = []

            def finish(j: int, shards: List[_Read]) -> Any:
                """Leaf ``j`` from its ``shards`` read, each CRC- and
                header-checked before any goes to ``put``."""
                entry = manifest["tensors"][names[j]]
                dtype, shape = np.dtype(entry["dtype"]), tuple(entry["shape"])
                like = flat[j][1]
                want = np.dtype(like.dtype) if hasattr(like, "dtype") else dtype
                views = _checked_rows(shards, dtype, shape, names[j])
                leaf, copied = _put(views, want, put)
                sp.add(host_copy_bytes=copied)
                return leaf

            read: List[_Read] = []
            for i, name in enumerate(names):
                for k, sh in enumerate(manifest["tensors"][name]["shards"]):
                    data = self.mnt.read_file(sh["path"])
                    sp.add(bytes=len(data))
                    crc = crc_worker.submit(_crc32, data)
                    if k == 0 and i > 0:
                        leaves.append(finish(i - 1, read))
                        read = []
                    read.append((sh, data, crc))
            if names:
                leaves.append(finish(len(names) - 1, read))
            return jax.tree_util.tree_unflatten(treedef, leaves), step


# a shard read: its manifest entry, its bytes and its CRC32 to come
_Read = Tuple[Dict[str, Any], bytes, Future]


def _crc32(data: bytes) -> int:
    """A shard's CRC32, on the restore's worker thread: timed there as the
    root span ``ckpt.crc32_work``, outside the restore's own spans."""
    with obs.span("ckpt.crc32_work", bytes=len(data)):
        return zlib.crc32(data) & 0xFFFFFFFF


def _checked_rows(read: List[_Read],
                  dtype: np.dtype, shape: Tuple[int, ...],
                  name: str) -> List[np.ndarray]:
    """The rows of each shard of a leaf, a view of the bytes read, once its
    CRC has matched the manifest's; together they must make the leaf's
    ``shape``."""
    views = []
    for sh, data, crc in read:
        with obs.span("ckpt.crc32", bytes=len(data), ready=int(crc.done())):
            got = crc.result()
        if got != sh["crc32"]:
            raise IOError(f"checksum mismatch in {sh['path']}")
        with obs.span("ckpt.decode", bytes=len(data)):
            views.append(_rows(data, dtype, shape, sh["path"]))
    if _joined_shape(views) != shape:
        raise IOError(f"the shards of {name} hold rows of shape "
                      f"{_joined_shape(views)}, not {shape}")
    return views


def _put(views: List[np.ndarray], want: np.dtype,
         put: Callable[[np.ndarray], Any]) -> Tuple[Any, int]:
    """One leaf: each shard's rows handed to ``put`` as they lie in the
    bytes read (copied only where a cast or the dtype's alignment asks for
    it), the results joined along dim 0 in their own library, and a host
    result that aliases the bytes read copied.  Returns the leaf and the
    bytes of rows copied on the host."""
    import jax
    import jax.numpy as jnp
    parts, copied = [], 0
    for v in views:
        if v.dtype != want or not v.flags.aligned:
            copied += v.nbytes
            with obs.span("ckpt.decode"):
                v = v.astype(want)
        parts.append(v)
    stored = sum(v.nbytes for v in views)
    with obs.span("ckpt.put", bytes=sum(p.nbytes for p in parts)):
        outs = [put(p) for p in parts]
        if len(outs) > 1 and isinstance(outs[0], jax.Array):
            return jnp.concatenate(outs), copied
        if len(outs) > 1:
            return np.concatenate(outs), stored
        leaf = outs[0]
        if isinstance(leaf, np.ndarray) and np.may_share_memory(leaf,
                                                                views[0]):
            return leaf.copy(), stored
        return leaf, copied
