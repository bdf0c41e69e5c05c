"""Model-level correctness invariants:
  * ``api.grow_cache`` gives a prefill cache ``api.cache_sds``'s layout
  * prefill+decode == full prefill (KV-cache/state consistency) per arch
  * causality: future tokens cannot influence past logits
  * MoE degenerates to a dense MLP for E=1/k=1
  * mamba's per-chunk SSM params compute the full-sequence ones' math
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MoEConfig
from repro.configs.registry import get_config, list_archs
from repro.models import api, lm, layers, mamba, moe
from repro.models.params import init_params


def _setup(arch):
    cfg = get_config(arch, smoke=True)
    params = api.init(cfg, jax.random.PRNGKey(1))
    return cfg, params


def _batch(cfg, toks, key):
    """A prefill batch of ``toks``, with stub frames for an enc-dec model."""
    batch = {"tokens": toks}
    if api.is_encdec(cfg):
        batch["frames"] = jax.random.normal(
            key, (toks.shape[0], cfg.encoder.num_frames, cfg.d_model),
            jnp.float32).astype(cfg.dtype) * 0.1
    return batch


@pytest.mark.parametrize("arch", list_archs())
def test_grow_cache_matches_cache_sds(arch):
    """A prefill cache grown to ``max_len`` has exactly the decode cache's
    structure, shapes and dtypes; each leaf keeps its prefill entries, the
    new slots are zero, and whisper's cross k/v pass bit-identical."""
    cfg, params = _setup(arch)
    B, T, max_len = 2, 8, 13
    key = jax.random.PRNGKey(4)
    toks = jax.random.randint(key, (B, T), 0, cfg.vocab_size)
    _, cache = api.prefill(cfg, params, _batch(cfg, toks, key))
    grown = api.grow_cache(cfg, cache, max_len)

    want = api.cache_sds(cfg, B, max_len)
    assert jax.tree.structure(grown) == jax.tree.structure(want)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), grown) == \
        jax.tree.map(lambda s: (s.shape, s.dtype), want)
    for before, after, axes in zip(jax.tree.leaves(cache), jax.tree.leaves(grown),
                                   jax.tree.leaves(api.cache_axes(cfg),
                                                   is_leaf=lambda x: isinstance(x, tuple))):
        if "cache_seq" not in axes:
            assert after is before
            continue
        seq = axes.index("cache_seq")
        assert before.shape[seq] == T
        np.testing.assert_array_equal(
            np.asarray(jax.lax.slice_in_dim(after, 0, T, axis=seq), np.float32),
            np.asarray(before, np.float32))
        assert not np.asarray(jax.lax.slice_in_dim(after, T, max_len, axis=seq)).any()
    if api.is_encdec(cfg):     # cross k/v: the very arrays the prefill made
        assert all(a is b for a, b in zip(jax.tree.leaves(grown["cross"]),
                                          jax.tree.leaves(cache["cross"])))


# The capacity-dispatch MoE archs: a full prefill on more tokens can drop
# assignments that the one-token decode serves. At T=3 the 2 x 4 tokens are
# no more than the smallest capacity (8), so no expert can overflow.
CAPACITY_T = {"olmoe-1b-7b": 3, "qwen2-moe-a2.7b": 3}


@pytest.mark.parametrize("arch", list_archs())
def test_decode_matches_prefill(arch):
    """Prefill on T tokens then decode token T must equal prefill on T+1."""
    cfg, params = _setup(arch)
    T = CAPACITY_T.get(arch, 16)
    key = jax.random.PRNGKey(7)
    toks = jax.random.randint(key, (2, T + 1), 0, cfg.vocab_size)

    full_logits, _ = api.prefill(cfg, params, _batch(cfg, toks, key))
    _, cache = api.prefill(cfg, params, _batch(cfg, toks[:, :T], key))
    cache = api.grow_cache(cfg, cache, T + 1)
    dec_logits, _ = api.decode_step(cfg, params, cache, toks[:, T:T + 1],
                                    jnp.asarray(T, jnp.int32))
    if arch in CAPACITY_T:
        for t in (toks, toks[:, :T]):
            _, _, aux = lm.forward(cfg, params, t, mode="prefill")
            # one dropped assignment would be >= 1/16; below that is the f32
            # rounding of 1 - mean(keep)
            assert abs(float(aux["moe_drop_frac"])) < 1e-6

    np.testing.assert_allclose(
        np.asarray(dec_logits, np.float32), np.asarray(full_logits, np.float32),
        atol=0.12, rtol=0.12)  # bf16 accumulation tolerance (deep stacks)


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
    p = init_params(moe.moe_defs(cfg), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 32, cfg.d_model),
                          jnp.float32)
    out, aux = moe.moe_apply(cfg, p, x)
    assert out.shape == x.shape
    # Switch LB loss is >= 1 (perfect balance) for softmax routing
    assert float(aux["moe_lb"]) >= 0.99
    assert 0.0 <= float(aux["moe_drop_frac"]) <= 1.0


def test_mamba_perchunk_paths_identical():
    """Both SSM-param paths (per-chunk vs full-seq) compute the same math
    (fp32 activations: bf16 would amplify benign op-ordering deltas)."""
    cfg = get_config("jamba-v0.1-52b", smoke=True).replace(dtype="float32")
    p = init_params(mamba.mamba_defs(cfg), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model),
                          jnp.float32) * 0.3
    outs = []
    for perchunk in (True, False):
        c = cfg.replace(mamba=dataclasses.replace(cfg.mamba,
                                                  perchunk_params=perchunk))
        y, _ = mamba.mamba_apply(c, p, x, mode="train")
        outs.append(np.asarray(y))
    np.testing.assert_allclose(outs[0], outs[1], atol=1e-6, rtol=1e-6)
