"""Compiles for a described TPU v5e, with no chip attached: the Pallas
kernels at qwen3-4b widths, and qwen3-4b's full-width decode step with the
weights in the serving dtype. Nothing runs; the TPU compiler refuses what
would not lower or not fit the chip's memory, as it would on the chip."""
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
