"""Sharded checkpointing: save/restore pytrees as npz shards + manifest,
async (background-thread) saves, rotation, and CSP-streamed restore; and the
CSP payload codec (``serialize``/``deserialize``), a raw typed wire format.
A tree whose leaves all live on one device is packed into that format on
the device and copied to the host once; ``serialize`` returns a bytes-like
object (``bytes`` or a ``memoryview``), never a copy made only to change
its type.

Fault-tolerance contract (exercised by launch/train.py --inject-failure):
  * saves are atomic (tmp dir + rename);
  * restore picks the latest complete step;
  * elastic restarts may restore onto a different mesh — values are host
    numpy, resharding happens at device_put against the new topology.
"""
from __future__ import annotations

import io
import json
import math
import shutil
import struct
import threading
import time
from pathlib import Path
from typing import Any, Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

PyTree = Any
_SEP = "::"


_NPZ_SAVABLE = {"float64", "float32", "float16", "int64", "int32", "int16",
                "int8", "uint8", "uint16", "uint32", "uint64", "bool"}

# CSP wire format: magic (format and version), header length, JSON header,
# then each leaf's raw bytes at an offset from the data start aligned to
# _ALIGN; the data starts at the header's end rounded up to _ALIGN.
_MAGIC = b"TRFCSP01"
_PREFIX = struct.Struct("<8sQ")
_ALIGN = 64
_UINT = {1: np.uint8, 2: np.uint16, 4: np.uint32}


def _aligned(n: int) -> int:
    return -(-n // _ALIGN) * _ALIGN


def _key(path) -> str:
    return _SEP.join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def _host_leaves(tree: PyTree) -> Iterator[Tuple[str, np.ndarray]]:
    """(key, host array) per leaf, each copy a ``truffle.csp.d2h``."""
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        with jax.profiler.TraceAnnotation("truffle.csp.d2h"):
            v = np.asarray(leaf)
        yield _key(path), v


def _flatten(tree: PyTree) -> Dict[str, np.ndarray]:
    flat = {}
    for key, v in _host_leaves(tree):
        if str(v.dtype) not in _NPZ_SAVABLE:   # bf16 etc. -> widen for npz
            with jax.profiler.TraceAnnotation("truffle.csp.widen"):
                v = v.astype(np.float32)
        flat[key] = v
    return flat


def _unflatten_into(like: PyTree, flat: Dict[str, np.ndarray]) -> PyTree:
    """``like``'s structure from ``flat``; a leaf whose dtype differs from
    ``like``'s is cast (``truffle.csp.narrow``), any other passes as it is."""
    paths = jax.tree_util.tree_flatten_with_path(like)
    leaves = []
    for path, leaf in paths[0]:
        key = _key(path)
        if key not in flat:
            raise KeyError(f"checkpoint missing leaf {key}")
        v = flat[key]
        if hasattr(leaf, "dtype") and v.dtype != leaf.dtype:
            with jax.profiler.TraceAnnotation("truffle.csp.narrow"):
                v = v.astype(leaf.dtype)
        leaves.append(v)
    return jax.tree_util.tree_unflatten(paths[1], leaves)


def _head(leaves) -> bytes:
    """Magic, header length, JSON header and padding to ``_ALIGN`` for
    ``[(key, dtype, shape, nbytes), ...]``: the payload up to its data."""
    entries, offset = [], 0
    for key, dtype, shape, nbytes in leaves:
        entries.append([key, str(dtype), list(shape), offset])
        offset += _aligned(nbytes)
    header = json.dumps({"leaves": entries}).encode()
    start = _PREFIX.size + len(header)
    return (_PREFIX.pack(_MAGIC, len(header)) + header
            + bytes(_aligned(start) - start))


def _packable(leaves) -> bool:
    """Whether the device can pack these leaves: each a ``jax.Array`` of 8-,
    16- or 32-bit items, all on one and the same device."""
    devices = set()
    for x in leaves:
        if (not isinstance(x, jax.Array)
                or jax.dtypes.itemsize_bits(x.dtype) not in (8, 16, 32)):
            return False
        devices |= x.devices()
    return len(devices) == 1


def _words(x: jax.Array) -> jax.Array:
    """``x``'s bytes in C order, zero-padded to ``_ALIGN``, as 1-D uint32
    words, little-endian like the host. Narrower items are paired into a
    word through the transpose: on the TPU the items of a 32-bit word lie
    along the second-minor axis, and pairing them along the minor axis
    would lay each pair out in a tile of its own."""
    if x.dtype == jnp.bool_:
        x = x.astype(jnp.uint8)
    size = x.dtype.itemsize
    u = lax.bitcast_convert_type(x, _UINT[size]).reshape(-1)
    if size < 4:
        rows = -(-u.size // 128)
        a = jnp.pad(u, (0, rows * 128 - u.size)).reshape(rows, 128)
        k = 4 // size
        items = a.T.reshape(128 // k, k, rows).transpose(0, 2, 1)
        u = lax.bitcast_convert_type(items, np.uint32).T.reshape(-1)
    n = _aligned(x.nbytes) // 4
    return jnp.pad(u[:n], (0, n - min(n, u.size)))


@jax.jit
def _pack(head: jax.Array, leaves) -> jax.Array:
    """The whole payload as one 1-D array of uint32 words: ``head``
    (``_head``'s bytes), then each leaf's ``_words``. The TPU tiles a 1-D
    array of 32-bit words in runs of consecutive words, so its copy to the
    host needs no change of layout, unlike one of 16-bit items."""
    return jnp.concatenate([head] + [_words(x) for x in leaves])


def serialize(tree: PyTree):
    """Whole-tree bytes (CSP payloads, storage uploads), each leaf in the
    dtype it has, as a bytes-like object. Layout: ``_MAGIC``, the header's
    length (little-endian uint64), a JSON header ``{"leaves": [[key, dtype,
    shape, offset], ...]}``, padding to ``_ALIGN``, then the leaves, each at
    ``offset`` from that point and padded to ``_ALIGN``.

    Where every leaf is a ``jax.Array`` on one device (``_packable``), the
    device packs the payload into 32-bit words (``_pack``, under
    ``truffle.csp.pack`` and ``.device_pack``) and one
    ``truffle.csp.d2h`` copies it to the host, which returns a read-only
    ``memoryview`` over that copy. Any other tree is copied to the host leaf
    by leaf (a ``truffle.csp.d2h`` each), then joined into ``bytes`` under
    ``truffle.csp.pack``, the only copy of the leaves' bytes."""
    with jax.profiler.TraceAnnotation("truffle.csp.serialize"):
        flat = [(_key(path), leaf) for path, leaf
                in jax.tree_util.tree_flatten_with_path(tree)[0]]
        if flat and _packable([leaf for _, leaf in flat]):
            with jax.profiler.TraceAnnotation("truffle.csp.pack"):
                head = _head([(k, x.dtype, x.shape, x.nbytes) for k, x in flat])
                with jax.profiler.TraceAnnotation("truffle.csp.device_pack"):
                    words = _pack(np.frombuffer(head, np.uint32),
                                  [x for _, x in flat])
            with jax.profiler.TraceAnnotation("truffle.csp.d2h"):
                return memoryview(np.asarray(words)).cast("B")
        raw = list(_host_leaves(tree))
        with jax.profiler.TraceAnnotation("truffle.csp.pack"):
            parts = [_head([(k, v.dtype, v.shape, v.nbytes) for k, v in raw])]
            for _, v in raw:
                # C-order bytes (a copy only if v is strided); bf16 exports no buffer
                parts += [v.reshape(-1).view(np.uint8),
                          bytes(_aligned(v.nbytes) - v.nbytes)]
            return b"".join(parts)


def deserialize(data: bytes, like: PyTree) -> PyTree:
    """The tree ``serialize`` wrote, as host arrays in ``like``'s dtypes:
    ``truffle.csp.unpack`` (the header, and a view per leaf), then
    ``truffle.csp.narrow`` for each leaf stored in another dtype. A leaf in
    its stored dtype is a read-only view over ``data``, not a copy. Raises
    ``ValueError`` for bytes of another format (such as npz), ``KeyError``
    for a leaf of ``like`` the payload lacks."""
    with jax.profiler.TraceAnnotation("truffle.csp.deserialize"):
        with jax.profiler.TraceAnnotation("truffle.csp.unpack"):
            if len(data) < _PREFIX.size or data[:len(_MAGIC)] != _MAGIC:
                raise ValueError("not a CSP payload: bad magic")
            _, n = _PREFIX.unpack_from(data)
            header = json.loads(bytes(data[_PREFIX.size:_PREFIX.size + n]))
            base = _aligned(_PREFIX.size + n)
            flat = {}
            for key, dtype, shape, offset in header["leaves"]:
                flat[key] = np.frombuffer(
                    data, jnp.dtype(dtype), count=math.prod(shape),
                    offset=base + offset).reshape(shape)
        return _unflatten_into(like, flat)


class CheckpointManager:
    def __init__(self, directory: str | Path, keep: int = 3,
                 shard_bytes: int = 512 << 20):
        self.dir = Path(directory)
        self.dir.mkdir(parents=True, exist_ok=True)
        self.keep = keep
        self.shard_bytes = shard_bytes
        self._inflight: Optional[threading.Thread] = None

    # ------------------------------------------------------------------ save
    def save(self, step: int, state: PyTree) -> None:
        flat = _flatten(state)
        tmp = self.dir / f".tmp-{step:08d}"
        if tmp.exists():
            shutil.rmtree(tmp)
        tmp.mkdir(parents=True)
        manifest = {"step": step, "shards": [], "time": time.time()}
        shard, size, idx = {}, 0, 0

        def flush():
            nonlocal shard, size, idx
            if not shard:
                return
            name = f"shard-{idx:04d}.npz"
            with open(tmp / name, "wb") as f:
                np.savez(f, **shard)
            manifest["shards"].append({"file": name, "keys": list(shard)})
            shard, size = {}, 0
            idx += 1

        for k, v in flat.items():
            shard[k] = v
            size += v.nbytes
            if size >= self.shard_bytes:
                flush()
        flush()
        (tmp / "manifest.json").write_text(json.dumps(manifest))
        final = self.dir / f"step-{step:08d}"
        if final.exists():
            shutil.rmtree(final)
        tmp.rename(final)                   # atomic publish
        self._rotate()

    def save_async(self, step: int, state: PyTree) -> threading.Thread:
        """Snapshot to host (blocking, cheap) then write in the background."""
        host_state = jax.tree.map(np.asarray, state)
        self.wait()
        t = threading.Thread(target=self.save, args=(step, host_state),
                             daemon=True, name=f"ckpt-save-{step}")
        t.start()
        self._inflight = t
        return t

    def wait(self) -> None:
        if self._inflight is not None:
            self._inflight.join()
            self._inflight = None

    def _rotate(self) -> None:
        steps = sorted(self.all_steps())
        for s in steps[:-self.keep]:
            shutil.rmtree(self.dir / f"step-{s:08d}", ignore_errors=True)

    # --------------------------------------------------------------- restore
    def all_steps(self):
        out = []
        for p in self.dir.glob("step-*"):
            if (p / "manifest.json").exists():
                out.append(int(p.name.split("-")[1]))
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def restore(self, like: PyTree, step: Optional[int] = None
                ) -> Tuple[PyTree, int]:
        step = step if step is not None else self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {self.dir}")
        d = self.dir / f"step-{step:08d}"
        flat: Dict[str, np.ndarray] = {}
        manifest = json.loads((d / "manifest.json").read_text())
        for sh in manifest["shards"]:
            with np.load(d / sh["file"]) as z:
                for k in z.files:
                    flat[k] = z[k]
        return _unflatten_into(like, flat), step

    def read_bytes(self, step: Optional[int] = None) -> bytes:
        """Raw checkpoint bytes (for CSP streaming to a restarting worker)."""
        step = step if step is not None else self.latest_step()
        d = self.dir / f"step-{step:08d}"
        buf = io.BytesIO()
        import zipfile
        with zipfile.ZipFile(buf, "w") as zf:
            for p in sorted(d.iterdir()):
                zf.write(p, p.name)
        return buf.getvalue()
