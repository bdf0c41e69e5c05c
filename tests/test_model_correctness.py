"""Model-level correctness invariants:
  * prefill+decode == full prefill (KV-cache/state consistency) per family
  * causality: future tokens cannot influence past logits
  * MoE degenerates to a dense MLP for E=1/k=1
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MoEConfig, ShapeConfig
from repro.configs.registry import get_config
from repro.models import api, lm, layers, moe

# one representative per cache family: GQA, MLA, hybrid(mamba), xLSTM, enc-dec,
# and MLA behind a leading dense layer with expert-parallel MoE
DECODE_FAMILIES = ["glm4-9b", "minicpm3-4b", "jamba-v0.1-52b", "xlstm-125m",
                   "whisper-medium", "deepseek-v2-lite"]


def _setup(arch):
    cfg = get_config(arch, smoke=True)
    params = api.init(cfg, jax.random.PRNGKey(1))
    return cfg, params


@pytest.mark.parametrize("arch", DECODE_FAMILIES)
def test_decode_matches_prefill(arch):
    """Prefill on T tokens then decode token T must equal prefill on T+1."""
    cfg, params = _setup(arch)
    T = 16
    key = jax.random.PRNGKey(7)
    toks = jax.random.randint(key, (2, T + 1), 0, cfg.vocab_size)

    if cfg.encoder is not None:
        frames = jax.random.normal(key, (2, cfg.encoder.num_frames, cfg.d_model),
                                   jnp.float32).astype(cfg.dtype) * 0.1
        full_logits, _ = api.prefill(cfg, params, {"frames": frames,
                                                   "tokens": toks})
        logits_T, cache = api.prefill(cfg, params, {"frames": frames,
                                                    "tokens": toks[:, :T]})
        # grow self cache to T+1 slots
        cache = {"self": jax.tree.map(
            lambda a: jnp.pad(a, ((0, 0), (0, 0), (0, 1), (0, 0), (0, 0))),
            cache["self"]), "cross": cache["cross"]}
        dec_logits, _ = api.decode_step(cfg, params, cache, toks[:, T:T + 1],
                                        jnp.asarray(T, jnp.int32))
    else:
        full_logits, _ = api.prefill(cfg, params, {"tokens": toks})
        logits_T, cache = api.prefill(cfg, params, {"tokens": toks[:, :T]})
        cache = _grow_cache(cfg, cache, extra=1)
        dec_logits, _ = api.decode_step(cfg, params, cache, toks[:, T:T + 1],
                                        jnp.asarray(T, jnp.int32))

    np.testing.assert_allclose(
        np.asarray(dec_logits, np.float32), np.asarray(full_logits, np.float32),
        atol=0.12, rtol=0.12)  # bf16 accumulation tolerance (deep stacks)


def _grow_cache(cfg, cache, extra):
    """Pad the sequence dim of attention caches by ``extra`` slots."""
    def pad(path, a):
        names = [str(getattr(p, "key", "")) for p in path]
        if names[-1] in ("k", "v"):           # [P,B,S,H,D]
            return jnp.pad(a, ((0, 0), (0, 0), (0, extra), (0, 0), (0, 0)))
        if names[-1] in ("ckv", "kpe"):       # [P,B,S,R]
            return jnp.pad(a, ((0, 0), (0, 0), (0, extra), (0, 0)))
        return a
    return jax.tree_util.tree_map_with_path(pad, cache)


@pytest.mark.parametrize("arch", ["qwen3-4b", "jamba-v0.1-52b", "xlstm-125m"])
def test_causality(arch):
    cfg, params = _setup(arch)
    key = jax.random.PRNGKey(3)
    toks = jax.random.randint(key, (1, 24), 0, cfg.vocab_size)
    toks2 = toks.at[:, -4:].set((toks[:, -4:] + 7) % cfg.vocab_size)

    h1, _, _ = lm.forward(cfg, params, toks, mode="train")
    h2, _, _ = lm.forward(cfg, params, toks2, mode="train")
    # positions before the edit are bit-identical
    np.testing.assert_array_equal(np.asarray(h1[:, :20], np.float32),
                                  np.asarray(h2[:, :20], np.float32))
    assert not np.allclose(np.asarray(h1[:, -1], np.float32),
                           np.asarray(h2[:, -1], np.float32))


def test_moe_single_expert_equals_dense_mlp():
    cfg = get_config("olmoe-1b-7b", smoke=True).replace(
        moe=MoEConfig(num_experts=1, top_k=1, d_expert=128,
                      capacity_factor=2.0))
    key = jax.random.PRNGKey(0)
    from repro.models.params import init_params
    p = init_params(moe.moe_defs(cfg), key)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 8, cfg.d_model),
                          jnp.float32) * 0.3
    out, aux = moe.moe_apply(cfg, p, x)
    # same weights through the plain MLP path
    mlp_p = {"wi_gate": p["w_gate"][0], "wi_up": p["w_up"][0],
             "wo": p["w_down"][0]}
    want = layers.apply_mlp(cfg, mlp_p, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    assert float(aux["moe_drop_frac"]) == 0.0


def test_moe_load_balance_loss_range():
    cfg = get_config("olmoe-1b-7b", smoke=True)
    from repro.models.params import init_params
    p = init_params(moe.moe_defs(cfg), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 32, cfg.d_model),
                          jnp.float32)
    out, aux = moe.moe_apply(cfg, p, x)
    assert out.shape == x.shape
    # Switch LB loss is >= 1 (perfect balance) for softmax routing
    assert float(aux["moe_lb"]) >= 0.99
    assert 0.0 <= float(aux["moe_drop_frac"]) <= 1.0
