"""Plain reference for decoder-only transformers with grouped-query attention
(Qwen3, GLM-4): the whole forward pass over a full sequence, in float32 at
``highest`` matmul precision, with no cache, no kernels and no batching of
requests into one program.

It imports nothing of the program. It reads the weight arrays the benchmark
made (``bench/model.py``), by their names in the program's parameter tree,
and casts each layer's weights to float32 as it reaches that layer, so that it
fits next to the served bf16 weights.

Layer equations, per the published models:

  h   = rmsnorm(x) * g1
  q,k,v = h Wq (+bq), h Wk (+bk), h Wv (+bv)      bias: GLM-4's add_qkv_bias
  q,k = rmsnorm_head(q) * gq, rmsnorm_head(k) * gk   Qwen3 only (qk_norm)
  q,k = rope(q), rope(k) over the first partial_rotary * head_dim dims
  x   = x + softmax(q k^T / sqrt(head_dim), causal) v Wo
  x   = x + (silu(h2 Wg) * (h2 Wu)) Wd,  h2 = rmsnorm(x) * g2
  logits = rmsnorm(x) * gf  @  (embedding^T if tied else Wout)

Departure from GLM-4 as published: ChatGLM rotates interleaved pairs of
dimensions (2i, 2i+1); this reference, like the program, rotates the pairs
(i, i + rot/2). The two are the same map under one fixed permutation of the
q and k columns, which a checkpoint converter applies; on random weights they
are the same family of models.

``fp8=True`` computes the same pass with every matmul operand rounded to
float8 e4m3 (a scale per row or column): the control that decides whether a
comparison can tell a lower precision than bf16 from the served path.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

# The sizes and switches this reference takes, by the names it reads them
# under; a configuration file states each (``bench.model.canonical``).
KEYS = ("num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim",
        "d_ff", "vocab_size", "norm_eps", "rope_theta", "tie_embeddings",
        "qkv_bias", "qk_norm", "partial_rotary")

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
# Bytes of attention scores one call may hold; rows are processed in blocks.
SCORE_BYTES = 1.5e9
VOCAB_CHUNKS = 4
FP8_MAX = 448.0


def _q8(a: jax.Array, axis: int) -> jax.Array:
    amax = jnp.max(jnp.abs(a), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (a / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(a, b, fp8: bool):
    if fp8:
        a, b = _q8(a, -1), _q8(b, -2)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, cos, sin):
    """x [r, T, H, hd]; cos/sin [T, rot/2]; rotates pairs (i, i + rot/2)."""
    half = cos.shape[-1]
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], -1)


class Reference:
    """Float32 forward pass of one configuration (``bench.model.canonical``
over ``KEYS``)."""

    def __init__(self, c: Dict[str, Any], fp8: bool = False):
        self.c = dict(c)
        self.fp8 = fp8
        hd = c["head_dim"]
        rot = int(c["partial_rotary"] * hd)
        self.rot = rot - rot % 2
        self._layer = jax.jit(self._layer_fn)
        self._embed = jax.jit(lambda table, t: jnp.take(table, t, axis=0).astype(F32))
        self._final = jax.jit(self._final_fn, static_argnums=(4,))

    # ---------------------------------------------------------------- layers
    def _layer_fn(self, x, blocks, l, cos, sin):
        c, fp8 = self.c, self.fp8
        p = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, l, 0, False).astype(F32),
            blocks["pos0"])
        r, T, D = x.shape
        nq, nkv, hd = c["num_heads"], c["num_kv_heads"], c["head_dim"]
        eps = c["norm_eps"]
        a = p["mixer"]
        h = _rms(x, p["mixer_norm"]["scale"], eps)
        q, k, v = _mm(h, a["wq"], fp8), _mm(h, a["wk"], fp8), _mm(h, a["wv"], fp8)
        if c["qkv_bias"]:
            q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
        q = q.reshape(r, T, nq, hd)
        k = k.reshape(r, T, nkv, hd)
        v = v.reshape(r, T, nkv, hd)
        if c["qk_norm"]:
            q = _rms(q, a["q_norm"], eps)
            k = _rms(k, a["k_norm"], eps)
        if self.rot:
            q, k = _rope(q, cos, sin), _rope(k, cos, sin)
        g = nq // nkv
        qg = q.reshape(r, T, nkv, g, hd)
        if fp8:
            qg, k, v = _q8(qg, -1), _q8(k, -1), _q8(v, 1)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k, precision=HIGHEST) * hd ** -0.5
        causal = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(causal, s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        if fp8:
            w = _q8(w, -1)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", w, v, precision=HIGHEST)
        x = x + _mm(o.reshape(r, T, nq * hd), a["wo"], fp8)
        m = p["mlp"]
        h = _rms(x, p["mlp_norm"]["scale"], eps)
        u = jax.nn.silu(_mm(h, m["wi_gate"], fp8)) * _mm(h, m["wi_up"], fp8)
        return x + _mm(u, m["wo"], fp8)

    def _final_fn(self, x, gf, table, i, tied: bool):
        """Logits of one vocabulary chunk ``i`` for hidden states x [r, P, D]."""
        h = _rms(x, gf.astype(F32), self.c["norm_eps"])
        n = table.shape[0 if tied else 1] // VOCAB_CHUNKS
        w = jax.lax.dynamic_slice_in_dim(table, i * n, n, 0 if tied else 1)
        w = w.astype(F32)
        return _mm(h, w.T if tied else w, self.fp8)

    def _tables(self, T: int):
        pos = jnp.arange(T, dtype=F32)
        half = self.rot // 2
        freq = 1.0 / (self.c["rope_theta"] ** (jnp.arange(half, dtype=F32) / max(half, 1)))
        ang = pos[:, None] * freq
        return jnp.cos(ang), jnp.sin(ang)

    # ----------------------------------------------------------------- entry
    def logits(self, weights, tokens: np.ndarray, positions: Sequence[int]) -> np.ndarray:
        """tokens [R, T] -> float32 logits [R, len(positions), V] at the given
        positions (each predicts the token after it)."""
        tokens = np.asarray(tokens, np.int32)
        R, T = tokens.shape
        pos = jnp.asarray(np.asarray(positions, np.int32))
        rows = max(1, min(R, int(SCORE_BYTES // (self.c["num_heads"] * T * T * 4))))
        cos, sin = self._tables(T)
        emb = weights["embed"]
        tied = bool(self.c["tie_embeddings"])
        table = emb["embedding"] if tied else emb["unembed"]
        out = []
        with jax.default_matmul_precision("highest"):
            for r0 in range(0, R, rows):
                blk = tokens[r0:r0 + rows]
                n = len(blk)
                if n < rows:                         # keep one compiled shape
                    blk = np.concatenate([blk, np.repeat(blk[:1], rows - n, 0)])
                x = self._embed(emb["embedding"], jnp.asarray(blk))
                for l in range(self.c["num_layers"]):
                    x = self._layer(x, weights["blocks"], jnp.int32(l), cos, sin)
                xs = x[:, pos]
                chunks = [self._final(xs, weights["final_norm"]["scale"], table,
                                      jnp.int32(i), tied)
                          for i in range(VOCAB_CHUNKS)]
                out.append(np.asarray(jnp.concatenate(chunks, -1))[:n])
                del x, xs, chunks
        return np.concatenate(out, 0)


@functools.lru_cache(maxsize=None)
def _cached(key, fp8: bool) -> Reference:
    return Reference(dict(key), fp8)


def reference(c: Dict[str, Any], fp8: bool = False) -> Reference:
    """One Reference per configuration and precision, so its programs are
    traced once per process."""
    return _cached(tuple(sorted(c.items())), fp8)
