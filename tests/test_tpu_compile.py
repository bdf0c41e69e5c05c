"""Compiles for a described TPU v5e, with no chip attached: the Pallas
kernels at qwen3-4b widths, qwen3-4b's full-width decode step with the
weights in the serving dtype, and DeepSeek-V2-Lite's expert-parallel share
(the grouped matmul of megablox over the stack of every layer's experts). Nothing runs; the TPU compiler refuses what
would not lower or not fit the chip's memory, as it would on the chip."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.launch.serve import serving_config
from repro.models import api

HBM_LIMIT_BYTES = 15.75 * 2 ** 30   # what XLA lets a program use on one v5e
B, HQ, HKV, D = 1, 32, 8, 128       # qwen3-4b attention widths


@pytest.fixture(scope="module")
def one_chip():
    """Device 0 of a described v5e:2x2, with the persistent compilation
    cache off: a TPU executable written there cannot be read back here."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was_enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was_enabled)
            cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_flash_attention_kernel_compiles(one_chip):
    S = 2048
    q = _sds((B, S, HQ, D), jnp.bfloat16, one_chip)
    kv = _sds((B, S, HKV, D), jnp.bfloat16, one_chip)
    compiled = _compile(lambda q, k, v: ops.flash_attention(q, k, v, True, False),
                        q, kv, kv)
    assert "tpu_custom_call" in compiled.as_text()


def test_decode_attention_kernel_compiles(one_chip):
    batch, S = 4, 4096
    q = _sds((batch, 1, HQ, D), jnp.bfloat16, one_chip)
    kv = _sds((batch, S, HKV, D), jnp.bfloat16, one_chip)
    kv_len = _sds((), jnp.int32, one_chip)
    compiled = _compile(lambda q, k, v, n: ops.decode_attention(q, k, v, n),
                        q, kv, kv, kv_len)
    assert "tpu_custom_call" in compiled.as_text()


def test_rmsnorm_kernel_compiles(one_chip):
    x = _sds((2048, 2560), jnp.bfloat16, one_chip)
    scale = _sds((2560,), jnp.float32, one_chip)
    compiled = _compile(lambda x, s: ops.rmsnorm(x, s), x, scale)
    assert "tpu_custom_call" in compiled.as_text()


def test_full_width_decode_step_fits_one_chip(one_chip):
    cfg = serving_config("qwen3-4b", smoke=False)
    assert (cfg.num_layers, cfg.d_model) == (36, 2560)
    assert cfg.param_dtype == cfg.dtype == "bfloat16"

    def on_chip(tree):
        return jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip), tree)

    batch, slots = 4, 256
    compiled = _compile(
        lambda p, c, t, pos: api.decode_step(cfg, p, c, t, pos),
        on_chip(api.abstract(cfg)), on_chip(api.cache_sds(cfg, batch, slots)),
        _sds((batch, 1), jnp.int32, one_chip), _sds((), jnp.int32, one_chip))
    args = compiled.memory_analysis().argument_size_in_bytes
    assert args < HBM_LIMIT_BYTES, args


@pytest.mark.parametrize("tokens", [128, 8192])
def test_expert_share_grouped_matmul_compiles(one_chip, tokens):
    """One chip's share of a DeepSeek-V2-Lite MoE layer (16 of 64 experts)
    at a decode step's 128 tokens and a prefill block's 8192, reading its
    experts from the stack of all 26 layers: the TPU kernel lowers (not its
    interpreted form) and fits the kernel's VMEM at the chosen tiles."""
    from repro.models import moe
    from repro.models.params import abstract_params

    base = serving_config("deepseek-v2-lite", smoke=False)
    cfg = base.replace(moe=dataclasses.replace(base.moe, num_experts=16))
    p = jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip),
                     abstract_params(moe.moe_defs(cfg), cfg.param_dtype))
    stack = {n: _sds((26,) + p[n].shape, p[n].dtype, one_chip)
             for n in moe.EXPERT_WEIGHTS}
    x = _sds((tokens, 1, cfg.d_model), jnp.bfloat16, one_chip)
    compiled = _compile(
        lambda p, s, x: moe.moe_apply(cfg, p, x, mode="decode",
                                      experts=(s, jnp.int32(25)))[0],
        p, stack, x)
    assert "tpu_custom_call" in compiled.as_text()
