"""Plain reference for decoders with multi-head latent attention and routed
plus shared experts after leading dense layers (DeepSeek-V2-Lite): the whole
forward pass over a full sequence, in float32 at ``highest`` matmul
precision, with no cache, no kernels, no grouped matmul and no batching of
requests into one program.

It imports nothing of the program. It reads the weight arrays the benchmark
made (``bench/model.py``), by their names in the program's parameter tree:
``lead`` (the leading dense layers) and ``blocks/pos0`` (the MoE layers),
each stacked on a leading layer axis, and casts each layer's weights to
float32 as it reaches that layer.

Layer equations, per DeepSeek-V2 (modeling_deepseek.py):

  h    = rmsnorm(x) * g1
  q    = h Wq                           [H, nope + rope]; no query low rank
  c,kr = h Wkv_a                        latent [kv_lora], shared rope key [rope]
  c    = rmsnorm(c) * gkv
  k,v  = c Wkv_b                        [H, nope], [H, v_head]
  q_r, k_r = yarn_rope(q_r), yarn_rope(kr)
  s    = (q_n k_n^T + q_r k_r^T) * scale, causal;  scale = (nope + rope)^-1/2
         * mscale(factor, mscale_all_dim)^2
  x    = x + softmax(s) v Wo
  h2   = rmsnorm(x) * g2
  dense layer:  x = x + (silu(h2 Wg) * (h2 Wu)) Wd
  MoE layer:    p = softmax(h2 Wr) over all R routed experts; top-k greedy;
                x = x + sum over the top-k experts e held here (e < E) of
                p_e * (silu(h2 Wg_e) * (h2 Wu_e)) Wd_e
                  + (silu(h2 Wsg) * (h2 Wsu)) Wsd        (shared experts)
  logits = rmsnorm(x) * gf @ Wout

YaRN: the rope frequency of dimension i is theta^(-2i/d), divided by
``factor`` above the ramp from ``low`` to ``high`` and blended linearly on it
(``DeepseekV2YarnRotaryEmbedding``); cos and sin are scaled by
mscale(factor, mscale) / mscale(factor, mscale_all_dim).

The chip's share: the experts of ids [0, E) of the R the router scores are
the ones held (rank 0 of the deployment), and the layer adds only their part
of the routed sum, exactly as the program does; what the other ranks would
add is left out of both.

Departures from DeepSeek-V2-Lite as published: DeepSeek rotates interleaved
pairs of dimensions (2i, 2i+1) of the rope part; this reference, like the
program, rotates the pairs (i, i + rope/2). The two are the same map under
one fixed permutation of the rope columns of Wq and Wkv_a, which a checkpoint
converter applies; on random weights they are the same family of models.
``routed_scaling_factor`` is 1 and ``norm_topk_prob`` false, so the top-k
weights are the router's probabilities themselves.

``fp8=True`` computes the same pass with every matmul operand rounded to
float8 e4m3 (a scale per row or column): the control that decides whether a
comparison can tell a lower precision than bf16 from the served path.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

# The sizes and switches this reference takes, by the names it reads them
# under; a configuration file states each (``bench.model.canonical``).
KEYS = ("num_layers", "leading_dense_layers", "d_model", "num_heads", "d_ff",
        "vocab_size", "norm_eps", "rope_theta", "tie_embeddings",
        "mla.q_lora_rank", "mla.kv_lora_rank", "mla.qk_nope_head_dim",
        "mla.qk_rope_head_dim", "mla.v_head_dim",
        "moe.num_experts", "moe.router_experts", "moe.top_k", "moe.d_expert",
        "moe.num_shared", "moe.norm_topk_prob",
        "yarn.factor", "yarn.original_max_position_embeddings",
        "yarn.beta_fast", "yarn.beta_slow", "yarn.mscale", "yarn.mscale_all_dim")

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
# Bytes of attention scores, and of one expert projection's activations, one
# call may hold; rows are processed in blocks.
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
    """x [r, T, H, rope]; cos/sin [T, rope/2]; rotates pairs (i, i + rope/2)."""
    half = cos.shape[-1]
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_ramp(c: Dict[str, Any]):
    """(low, high): YaRN's correction range over the rope dimensions."""
    dim, base = c["mla.qk_rope_head_dim"], c["rope_theta"]
    orig = c["yarn.original_max_position_embeddings"]

    def corr(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(base))

    return (max(math.floor(corr(c["yarn.beta_fast"])), 0),
            min(math.ceil(corr(c["yarn.beta_slow"])), dim - 1))


def softmax_scale(c: Dict[str, Any]) -> float:
    scale = (c["mla.qk_nope_head_dim"] + c["mla.qk_rope_head_dim"]) ** -0.5
    if c["yarn.mscale_all_dim"]:
        scale *= _mscale(c["yarn.factor"], c["yarn.mscale_all_dim"]) ** 2
    return scale


class Reference:
    """Float32 forward pass of one configuration (``bench.model.canonical``
over ``KEYS``)."""

    def __init__(self, c: Dict[str, Any], fp8: bool = False):
        if c["mla.q_lora_rank"] is not None:
            raise ValueError("this reference takes a full-rank query projection "
                             "(q_lora_rank null)")
        self.c = dict(c)
        self.fp8 = fp8
        self.rot = c["mla.qk_rope_head_dim"]
        self._layer = jax.jit(functools.partial(self._layer_fn, moe=True))
        self._lead = jax.jit(functools.partial(self._layer_fn, moe=False))
        self._embed = jax.jit(lambda table, t: jnp.take(table, t, axis=0).astype(F32))
        self._final = jax.jit(self._final_fn)

    # ---------------------------------------------------------------- layers
    def _attention(self, x, p, cos, sin):
        c, fp8 = self.c, self.fp8
        r, T, _ = x.shape
        H = c["num_heads"]
        nope, rope = c["mla.qk_nope_head_dim"], c["mla.qk_rope_head_dim"]
        R, vd = c["mla.kv_lora_rank"], c["mla.v_head_dim"]
        a = p["mixer"]
        h = _rms(x, p["mixer_norm"]["scale"], c["norm_eps"])
        q = _mm(h, a["wq"], fp8).reshape(r, T, H, nope + rope)
        kv_a = _mm(h, a["wkv_a"], fp8)
        lat = _rms(kv_a[..., :R], a["kv_norm"], c["norm_eps"])
        k_r = _rope(kv_a[..., None, R:], cos, sin)[:, :, 0]          # [r, T, rope]
        q_n, q_r = q[..., :nope], _rope(q[..., nope:], cos, sin)
        kv = _mm(lat, a["wkv_b"], fp8).reshape(r, T, H, nope + vd)
        k_n, v = kv[..., :nope], kv[..., nope:]
        if fp8:
            q_n, q_r, k_n, k_r, v = (_q8(q_n, -1), _q8(q_r, -1), _q8(k_n, -1),
                                     _q8(k_r, -1), _q8(v, 1))
        s = (jnp.einsum("bqhd,bkhd->bhqk", q_n, k_n, precision=HIGHEST)
             + jnp.einsum("bqhd,bkd->bhqk", q_r, k_r, precision=HIGHEST))
        s = s * softmax_scale(c)
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        if fp8:
            w = _q8(w, -1)
        o = jnp.einsum("bhqk,bkhd->bqhd", w, v, precision=HIGHEST)
        return x + _mm(o.reshape(r, T, H * vd), a["wo"], fp8)

    def _swiglu(self, h, m):
        fp8 = self.fp8
        u = jax.nn.silu(_mm(h, m["wi_gate"], fp8)) * _mm(h, m["wi_up"], fp8)
        return _mm(u, m["wo"], fp8)

    def _experts(self, h, m):
        """The held experts' part of the routed sum, every held expert over
        every token, weighted by its gate (zero where not in the top-k)."""
        c, fp8 = self.c, self.fp8
        E = c["moe.num_experts"]
        probs = jax.nn.softmax(_mm(h, m["router"], fp8), axis=-1)   # [r, T, R]
        top_p, top_i = jax.lax.top_k(probs, c["moe.top_k"])
        if c["moe.norm_topk_prob"]:
            top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
        gate = jnp.einsum("rtk,rtke->rte", top_p, jax.nn.one_hot(top_i, E, dtype=F32))
        wg, wu, wd = m["w_gate"], m["w_up"], m["w_down"]
        if fp8:
            h, wg, wu, wd = _q8(h, -1), _q8(wg, -2), _q8(wu, -2), _q8(wd, -2)
        u = (jax.nn.silu(jnp.einsum("rtd,edf->rtef", h, wg, precision=HIGHEST))
             * jnp.einsum("rtd,edf->rtef", h, wu, precision=HIGHEST))
        u = u * gate[..., None]
        if fp8:
            u = _q8(u, -1)
        return jnp.einsum("rtef,efd->rtd", u, wd, precision=HIGHEST)

    def _layer_fn(self, x, stack, l, cos, sin, moe: bool):
        p = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, l, 0, False).astype(F32),
            stack["pos0"] if moe else stack)
        x = self._attention(x, p, cos, sin)
        h = _rms(x, p["mlp_norm"]["scale"], self.c["norm_eps"])
        m = p["mlp"]
        if not moe:
            return x + self._swiglu(h, m)
        return x + self._experts(h, m) + self._swiglu(h, m["shared"])

    def _final_fn(self, x, gf, table, i):
        """Logits of one vocabulary chunk ``i`` for hidden states x [r, P, D]."""
        h = _rms(x, gf.astype(F32), self.c["norm_eps"])
        n = table.shape[1] // VOCAB_CHUNKS
        w = jax.lax.dynamic_slice_in_dim(table, i * n, n, 1).astype(F32)
        return _mm(h, w, self.fp8)

    def _tables(self, T: int):
        """YaRN cos/sin [T, rope/2], as ``DeepseekV2YarnRotaryEmbedding``."""
        c = self.c
        dim, base, factor = self.rot, c["rope_theta"], c["yarn.factor"]
        pair = jnp.arange(0, dim, 2, dtype=F32) / dim
        freq_extra = 1.0 / (base ** pair)
        freq_inter = 1.0 / (factor * base ** pair)
        low, high = yarn_ramp(c)
        ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low)
                        / (high - low if high != low else 0.001), 0, 1)
        extra = 1.0 - ramp
        freq = freq_inter * (1 - extra) + freq_extra * extra
        ang = jnp.arange(T, dtype=F32)[:, None] * freq
        m = (_mscale(factor, c["yarn.mscale"])
             / _mscale(factor, c["yarn.mscale_all_dim"]))
        return jnp.cos(ang) * m, jnp.sin(ang) * m

    # ----------------------------------------------------------------- entry
    def logits(self, weights, tokens: np.ndarray, positions: Sequence[int]) -> np.ndarray:
        """tokens [R, T] -> float32 logits [R, len(positions), V] at the given
        positions (each predicts the token after it)."""
        if self.c["tie_embeddings"]:
            raise ValueError("DeepSeek-V2-Lite's output head is untied")
        c = self.c
        tokens = np.asarray(tokens, np.int32)
        R, T = tokens.shape
        pos = jnp.asarray(np.asarray(positions, np.int32))
        per_row = max(c["num_heads"] * T * T,
                      T * c["moe.num_experts"] * c["moe.d_expert"]) * 4
        rows = max(1, min(R, int(SCORE_BYTES // per_row)))
        cos, sin = self._tables(T)
        emb = weights["embed"]
        n_lead = c["leading_dense_layers"]
        out = []
        with jax.default_matmul_precision("highest"):
            for r0 in range(0, R, rows):
                blk = tokens[r0:r0 + rows]
                n = len(blk)
                if n < rows:                         # keep one compiled shape
                    blk = np.concatenate([blk, np.repeat(blk[:1], rows - n, 0)])
                x = self._embed(emb["embedding"], jnp.asarray(blk))
                for l in range(n_lead):
                    x = self._lead(x, weights["lead"], jnp.int32(l), cos, sin)
                for l in range(c["num_layers"] - n_lead):
                    x = self._layer(x, weights["blocks"], jnp.int32(l), cos, sin)
                xs = x[:, pos]
                chunks = [self._final(xs, weights["final_norm"]["scale"],
                                      emb["unembed"], jnp.int32(i))
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
