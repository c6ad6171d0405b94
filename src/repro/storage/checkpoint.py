"""Sharded, crash-safe checkpointing THROUGH CFS (the paper's technique as a
first-class framework feature).

Layout on the volume:
    /ckpt/step_<N>.tmp/...              (in-flight)
    /ckpt/step_<N>/<leaf-path>.shard<k> (tensor shards, large-file extents)
    /ckpt/step_<N>/MANIFEST             (small file — aggregated extent path)
    /ckpt/LATEST                        (small file, atomic commit pointer)

Crash safety: data files first, MANIFEST second, LATEST last — a crash at
any point leaves the previous checkpoint loadable (tested with injected
crashes).  Every tensor carries a CRC32 in the manifest, verified on load
(the device-side Pallas ``checksum`` kernel plays this role on TPU).

Elasticity: tensors are split into ``shards`` along dim 0 where possible —
restore copies each shard into its rows of the leaf, so a
checkpoint written by H hosts loads on H' ≠ H (re-sharding happens at
device_put with the new mesh's shardings).
"""

from __future__ import annotations

import json
import math
import zlib
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from .. import obs
from ..core.client import NotFound
from ..core.fs import CfsMount

__all__ = ["CheckpointManager", "InjectedCrash", "tensor_to_bytes",
           "bytes_to_tensor"]

_MAGIC = b"RPT1"


class InjectedCrash(RuntimeError):
    """A crash injected on purpose (``crash_at`` / ``crash_after``).

    Launchers resume from the last checkpoint on this class only; any other
    error, a device fault among them, propagates."""


def tensor_to_bytes(arr: np.ndarray) -> bytes:
    header = json.dumps({"dtype": str(arr.dtype),
                         "shape": list(arr.shape)}).encode()
    raw = np.ascontiguousarray(arr).tobytes()
    return (_MAGIC + len(header).to_bytes(4, "little") + header + raw)


def bytes_to_tensor(data: bytes) -> np.ndarray:
    dtype, shape, offset = _parse_header(data)
    return np.frombuffer(data, dtype=dtype,
                         offset=offset).reshape(shape).copy()


def _parse_header(data: bytes) -> Tuple[np.dtype, Tuple[int, ...], int]:
    """The dtype and shape of a tensor file, and the offset of its rows."""
    if data[:4] != _MAGIC:
        raise IOError("bad tensor file")
    hlen = int.from_bytes(data[4:8], "little")
    header = json.loads(data[8 : 8 + hlen].decode())
    return np.dtype(header["dtype"]), tuple(header["shape"]), 8 + hlen


def _decode_into(data: bytes, leaf: np.ndarray, rows: np.ndarray,
                 path: str) -> int:
    """Copy the rows of the tensor file ``data`` to the front of ``rows``,
    the bytes of ``leaf`` from the file's first row on; return how many.
    The file must hold the leaf's dtype and trailing shape, and fit."""
    dtype, shape, offset = _parse_header(data)
    n = len(data) - offset
    if (dtype != leaf.dtype or len(shape) != leaf.ndim
            or shape[1:] != leaf.shape[1:] or n > rows.nbytes
            or n != math.prod(shape) * dtype.itemsize):
        raise IOError(f"{path} does not fit its leaf")
    rows[:n] = np.frombuffer(data, np.uint8, n, offset)
    return n


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
        ``ShapeDtypeStruct``s).  Each leaf is read, CRC-checked and handed
        to ``put`` (a device transfer, say) before the next one is read, so
        the host holds one leaf at a time."""
        import jax
        with obs.span("ckpt.restore") as sp:
            step = self.latest_step() if step is None else step
            if step is None:
                raise NotFound("no checkpoint")
            d = f"{self.base}/step_{step}"
            manifest = json.loads(
                self.mnt.read_file(f"{d}/MANIFEST").decode())
            flat, treedef = jax.tree_util.tree_flatten_with_path(tree_like)
            leaves = []
            for path, like in flat:
                name = _leaf_name(path)
                entry = manifest["tensors"][name]
                # a buffer of its own for every leaf: ``put`` may keep it
                arr = np.empty(entry["shape"], np.dtype(entry["dtype"]))
                rows = arr.reshape(-1).view(np.uint8)
                at = 0
                for sh in entry["shards"]:
                    data = self.mnt.read_file(sh["path"])
                    sp.add(bytes=len(data))
                    with obs.span("ckpt.crc32", bytes=len(data)):
                        crc = zlib.crc32(data) & 0xFFFFFFFF
                    if crc != sh["crc32"]:
                        raise IOError(f"checksum mismatch in {sh['path']}")
                    with obs.span("ckpt.decode", bytes=len(data)):
                        at += _decode_into(data, arr, rows[at:], sh["path"])
                if at != rows.nbytes:
                    raise IOError(f"the shards of {name} hold {at} of its "
                                  f"{rows.nbytes} bytes")
                if hasattr(like, "dtype"):
                    with obs.span("ckpt.decode"):
                        arr = arr.astype(like.dtype, copy=False)
                with obs.span("ckpt.put", bytes=arr.nbytes):
                    leaves.append(put(arr))
            return jax.tree_util.tree_unflatten(treedef, leaves), step
