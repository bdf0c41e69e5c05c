"""Model / run configuration dataclasses.

Every assigned architecture is expressed as a ``ModelConfig``. The layer stack
is described by a repeating *superblock* pattern (``block_pattern``) so that
heterogeneous stacks (Jamba's 1:7 attn:mamba interleave, xLSTM's m/s pattern)
lower to a single ``lax.scan`` over ``num_layers // len(block_pattern)``
periods — compile time stays O(1) in depth.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional, Tuple


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head Latent Attention (DeepSeek-V2 / MiniCPM3 style).
    ``q_lora_rank`` None: the query is one full-rank projection ``wq``
    (DeepSeek-V2-Lite)."""
    q_lora_rank: Optional[int] = 768
    kv_lora_rank: int = 256
    qk_nope_head_dim: int = 64
    qk_rope_head_dim: int = 32
    v_head_dim: int = 64


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8
    top_k: int = 2
    d_expert: int = 0              # expert FFN hidden size (0 -> use d_ff)
    num_shared: int = 0            # shared (always-on) experts, each d_expert wide
    capacity_factor: float = 1.25  # train-time token capacity per expert
    router_z_coef: float = 1e-3
    aux_loss_coef: float = 1e-2
    norm_topk_prob: bool = True    # renormalise the top-k weights to sum to 1
    # Experts the router scores. 0: the layer holds every expert it routes
    # over, and serving keeps the training path's capacity dispatch. Set, the
    # layer is one rank's share of expert parallelism: it holds experts
    # [0, num_experts) of the router's ``router_experts``, adds only their
    # part of the result, and serves without drops (``moe.moe_apply``).
    router_experts: int = 0


@dataclass(frozen=True)
class YarnConfig:
    """YaRN rope scaling (DeepSeek-V2's ``rope_scaling`` of type yarn)."""
    factor: float = 1.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclass(frozen=True)
class MambaConfig:
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0               # 0 -> ceil(d_model / 16)
    chunk: int = 256               # scan chunk (memory/parallelism trade-off)
    # §Perf: compute SSM params (A_bar/Bx) per chunk inside the scan (True)
    # vs materializing them for the full sequence (False, paper-naive).
    perchunk_params: bool = True


@dataclass(frozen=True)
class XLSTMConfig:
    # positions (mod len(block_pattern)) handled via block_pattern entries
    mlstm_proj_factor: float = 2.0
    slstm_ffn_factor: float = 4.0 / 3.0
    chunk: int = 128               # mLSTM chunkwise-parallel chunk length


@dataclass(frozen=True)
class EncoderConfig:
    """Encoder stack for enc-dec models (whisper). Frontend is a STUB:
    ``input_specs`` supplies precomputed frame embeddings."""
    num_layers: int = 24
    num_frames: int = 1500         # whisper-medium: 30 s -> 1500 frames


@dataclass(frozen=True)
class VisionConfig:
    """VLM frontend STUB: precomputed patch embeddings + M-RoPE sections."""
    num_image_tokens: int = 1024
    mrope_sections: Tuple[int, int, int] = (16, 24, 24)  # t/h/w over head_dim/2


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | hybrid | ssm | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0              # 0 -> d_model // num_heads

    # --- attention ---
    attention_type: str = "gqa"    # gqa | mla
    qk_norm: bool = False
    qkv_bias: bool = False
    rope_theta: float = 1e4
    partial_rotary: float = 1.0    # fraction of head_dim that is rotated
    mla: Optional[MLAConfig] = None
    yarn: Optional[YarnConfig] = None

    # --- layer stack ---
    # One *superblock* period; each entry is (mixer, mlp):
    #   mixer in {attn, mamba, mlstm, slstm}; mlp in {mlp, moe, none, glu}
    # Dense default: (("attn", "mlp"),)
    block_pattern: Tuple[Tuple[str, str], ...] = (("attn", "mlp"),)
    # Layers of (attn, mlp) before the periodic stack (DeepSeek's
    # first_k_dense_replace), stacked on a leading axis of their own.
    leading_dense_layers: int = 0
    norm: str = "rmsnorm"          # rmsnorm | layernorm
    norm_eps: float = 1e-5
    act: str = "silu"              # silu (SwiGLU MLP) | gelu (plain MLP)
    tie_embeddings: bool = False

    moe: Optional[MoEConfig] = None
    mamba: Optional[MambaConfig] = None
    xlstm: Optional[XLSTMConfig] = None
    encoder: Optional[EncoderConfig] = None   # != None -> enc-dec (whisper)
    vision: Optional[VisionConfig] = None     # != None -> VLM (qwen2-vl)

    # --- numerics ---
    dtype: str = "bfloat16"        # activation/compute dtype (and the KV cache's)
    param_dtype: str = "float32"   # canonical parameter dtype
    remat: str = "dots"            # none | dots | full  (train-time only)
    loss_chunk: int = 2048         # vocab-loss computed over seq chunks (memory)

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def period(self) -> int:
        return len(self.block_pattern)

    @property
    def num_periods(self) -> int:
        n = self.num_layers - self.leading_dense_layers
        assert n % self.period == 0, (
            f"{self.name}: {n} layers after the {self.leading_dense_layers} "
            f"leading dense ones not divisible by block_pattern "
            f"period={self.period}")
        return n // self.period

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # ---- parameter count (for 6ND model-flops accounting) ----
    def param_count(self, active_only: bool = False) -> int:
        """Approximate parameter count (embedding included once)."""
        d, h = self.d_model, self.resolved_head_dim
        n_q, n_kv = self.num_heads, self.num_kv_heads
        total = self.vocab_size * d * (1 if self.tie_embeddings else 2)

        def attn_params() -> int:
            if self.attention_type == "mla":
                m = self.mla
                qdim = n_q * (m.qk_nope_head_dim + m.qk_rope_head_dim)
                q = (d * qdim if m.q_lora_rank is None
                     else d * m.q_lora_rank + m.q_lora_rank * qdim)
                return (q
                        + d * (m.kv_lora_rank + m.qk_rope_head_dim)
                        + m.kv_lora_rank * n_q * (m.qk_nope_head_dim + m.v_head_dim)
                        + n_q * m.v_head_dim * d)
            return d * (n_q * h) + 2 * d * (n_kv * h) + (n_q * h) * d

        def mlp_params(dff: int) -> int:
            n_mat = 3 if self.act == "silu" else 2
            return n_mat * d * dff

        def moe_params(active: bool) -> int:
            m = self.moe
            dff = m.d_expert or self.d_ff
            n_e = (m.top_k if active else m.num_experts) + m.num_shared
            return n_e * mlp_params(dff) + d * (m.router_experts or m.num_experts)

        def mamba_params() -> int:
            mc = self.mamba
            d_in = mc.expand * d
            dt_rank = mc.dt_rank or -(-d // 16)
            return (d * 2 * d_in + mc.d_conv * d_in
                    + d_in * (dt_rank + 2 * mc.d_state) + dt_rank * d_in
                    + d_in * mc.d_state + d_in + d_in * d)

        def mlstm_params() -> int:
            d_in = int(self.xlstm.mlstm_proj_factor * d)
            # up(2x), q/k/v, gates (i,f,o from x), down
            return d * 2 * d_in + 3 * d_in * d_in + 3 * d_in + d_in * d

        def slstm_params() -> int:
            dff = int(self.xlstm.slstm_ffn_factor * d)
            # 4 gates x (input + recurrent) + GLU ffn
            return 4 * (d * d + d * d // max(self.num_heads, 1)) + 3 * d * dff

        per_period = 0
        for mixer, mlp in self.block_pattern:
            per_period += {"attn": attn_params, "mamba": mamba_params,
                           "mlstm": mlstm_params, "slstm": slstm_params}[mixer]()
            if mlp == "mlp":
                per_period += mlp_params(self.d_ff)
            elif mlp == "moe":
                per_period += moe_params(active_only)
            elif mlp == "glu":
                per_period += mlp_params(int(self.xlstm.slstm_ffn_factor * d)) if self.xlstm else mlp_params(self.d_ff)
        total += per_period * self.num_periods
        total += self.leading_dense_layers * (attn_params() + mlp_params(self.d_ff))

        if self.encoder is not None:  # whisper: encoder self-attn + mlp, decoder cross-attn
            enc = self.encoder.num_layers * (attn_params() + mlp_params(self.d_ff))
            xattn = self.num_layers * attn_params()
            total += enc + xattn
        return int(total)


@dataclass(frozen=True)
class ShapeConfig:
    """A batch shape the train / prefill / decode entry points take
    (``api.input_specs``)."""
    name: str
    seq_len: int
    global_batch: int
    kind: str                      # train | prefill | decode
