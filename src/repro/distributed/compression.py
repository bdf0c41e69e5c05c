"""Compression for the two bandwidth-bound paths in the system:

1. Gradient compression for the DP all-reduce: int8 quantized
   reduce-scatter + all-gather with per-tensor scales and error feedback.
   Wire bytes vs fp32 ring all-reduce: ~4x less (1B/elem each way + scalar
   scales). Used inside a ``shard_map`` over the DP axes
   (``steps.build_train_step(..., dp_mode="shardmap_int8")``).

2. Chunk codecs for the Truffle data plane (:class:`ChunkCodec`): a WAN
   edge whose :class:`~repro.runtime.policy.DataPolicy` sets
   ``compression="lz4-like"`` ships compressed chunks through
   ``Channel.stream``/``transfer`` — the codec estimates the payload's
   compressibility from a sampled window and the channel grants only the
   compressed wire bytes. Pure stdlib; the data plane imports it lazily so
   runtime code paths never pay the jax import unless compression engages.
"""
from __future__ import annotations

import functools
import zlib
from dataclasses import dataclass
from typing import TYPE_CHECKING, Any, Optional, Tuple

if TYPE_CHECKING:                     # postponed annotations only
    import jax

PyTree = Any

# jax is imported INSIDE the gradient functions (not at module top): the
# Truffle data plane resolves ChunkCodec from this module, and a WAN edge
# enabling compression must not pay a ~1s ML-stack import on its first
# dispatch (it showed up as tens of simulated seconds at small clock
# scales).


# ------------------------------------------------------- data-plane codecs
@dataclass(frozen=True)
class ChunkCodec:
    """An lz4-like chunk codec model: fast, modest-ratio byte compression.

    ``ratio`` estimates the wire/payload byte ratio by deflating a sampled
    window (zlib level 1 ≈ an upper bound on what an lz4-class codec
    keeps); ``floor`` models the codec's framing overhead — even an
    all-zeros payload ships ~5% of its bytes. ``compress_bps`` is the
    codec's steady-state throughput (single core of the paper's 4-core
    Xeon edge VMs, ~100 MB/s with small chunks): pipelined compression
    hides behind links *slower* than the codec (every WAN tier), but on a
    link faster than the codec the transfer becomes codec-bound — the
    data plane paces the stream at ``compress_bps`` and the adaptive
    planner models it as an effective wire ratio of bandwidth/codec_bps.
    ``compress_s`` prices the startup (first-chunk) compression, the only
    codec time on the critical path of a pipelined wire-bound stream."""
    name: str
    level: int = 1
    floor: float = 0.05
    compress_bps: float = 1.0e8           # bytes/sec, single core
    sample_bytes: int = 64 * 1024
    #: compressibility probe: "deflate" (measure: zlib level 1 on the
    #: window — the default, what the pinned benchmark numbers were taken
    #: with) or "entropy" (estimate: the jax byte-histogram kernel in
    #: ``repro.kernels.ops`` — vectorizable/offloadable, but order-0 only:
    #: blind to match structure, so strictly an opt-in)
    estimator: str = "deflate"

    def ratio(self, data) -> float:
        view = bytes(memoryview(data)[:self.sample_bytes])
        if not view:
            return 1.0
        if self.estimator == "entropy":
            # lazy: the runtime data plane must not pay the ML-stack
            # import unless a plan actually selects the entropy codec
            from repro.kernels.ops import entropy_wire_ratio
            return entropy_wire_ratio(view, floor=self.floor)
        compressed = zlib.compress(view, self.level)
        return min(1.0, max(self.floor, len(compressed) / len(view)))

    def compress_s(self, nbytes: int) -> float:
        return max(nbytes, 0) / self.compress_bps


LZ4_LIKE = ChunkCodec("lz4-like")
#: same codec model, entropy-probed: the ratio estimate comes from the
#: jit'd byte-histogram kernel instead of deflating the sample window
LZ4_ENTROPY = ChunkCodec("lz4-entropy", estimator="entropy")
_CHUNK_CODECS = {"lz4-like": LZ4_LIKE, "lz4-entropy": LZ4_ENTROPY}


def chunk_codec(name: Optional[str]) -> Optional[ChunkCodec]:
    """Resolve a :class:`~repro.runtime.policy.DataPolicy.compression`
    value to a codec (``None``/"none" -> no codec)."""
    if name in (None, "none"):
        return None
    try:
        return _CHUNK_CODECS[name]
    except KeyError:
        raise KeyError(f"no chunk codec {name!r} "
                       f"(have: {sorted(_CHUNK_CODECS)})") from None


def quantize(x: jax.Array, bits: int = 8) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-tensor quantization -> (int8 codes, fp32 scale)."""
    import jax.numpy as jnp
    assert bits == 8, "int8 path only"
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    import jax.numpy as jnp
    return q.astype(jnp.float32) * scale


def quantization_error(x: jax.Array) -> jax.Array:
    """Residual for error feedback: x - dequant(quant(x))."""
    q, s = quantize(x)
    return x - dequantize(q, s)


def compressed_mean(x: jax.Array, axis_name: str) -> jax.Array:
    """Mean over ``axis_name`` using int8 RS+AG (call inside shard_map).

    Stage 1 (reduce-scatter): all_to_all int8 chunks; each device dequantizes
    its chunk from every peer (per-peer scales via a tiny fp32 all_gather)
    and reduces in fp32. Stage 2 (all-gather): requantize the reduced chunk
    and gather codes+scales."""
    import jax
    import jax.numpy as jnp
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x
    size = x.size
    chunk = -(-size // n)
    flat = jnp.pad(x.reshape(-1).astype(jnp.float32), (0, chunk * n - size))
    xs = flat.reshape(n, chunk)

    q, s = quantize(xs)
    qt = jax.lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0,
                            tiled=False)              # [n, chunk] peers' rows
    ss = jax.lax.all_gather(s, axis_name)             # [n]
    mine = jnp.sum(dequantize(qt, ss[:, None, None] if qt.ndim == 3
                              else ss[:, None]), axis=0) / n

    q2, s2 = quantize(mine)
    qg = jax.lax.all_gather(q2, axis_name)            # [n, chunk]
    sg = jax.lax.all_gather(s2, axis_name)            # [n]
    out = dequantize(qg, sg[:, None]).reshape(-1)[:size]
    return out.reshape(x.shape).astype(x.dtype)


def compressed_grad_sync(grads: PyTree, axis_name: str) -> PyTree:
    """Apply compressed_mean leaf-wise (large leaves only; small ones go
    fp32 — scales/biases are latency- not bandwidth-bound)."""
    import jax

    def sync(g):
        if g.size < 16384:
            return jax.lax.pmean(g, axis_name)
        return compressed_mean(g, axis_name)
    return jax.tree.map(sync, grads)
