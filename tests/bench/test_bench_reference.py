"""The plain reference against the program (repro.models) at smoke widths on
the CPU: prefill logits, and decode through the grown cache."""
from __future__ import annotations

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(Path(__file__).resolve().parent)]

from bench import harness, model  # noqa: E402
import tiny  # noqa: E402
from repro.serving.engine import ServeEngine  # noqa: E402

L, STEPS, B = 10, 4, 2


def _program_and_reference(name, dtype):
    config = tiny.tiny_config(name)
    refmod = harness.Bench().reference(config)
    c = model.canonical(config, refmod.KEYS)
    cfg = model.program_config(config, c).replace(dtype=dtype, param_dtype=dtype)
    weights = model.make_weights(cfg, 11)
    return cfg, weights, refmod.Reference(c), c


def _program_logits(cfg, weights, toks):
    """Prefill logits of the prompt, then one decode step per further token,
    through the engine's compiled programs: [B, STEPS + 1, V]."""
    eng = ServeEngine(cfg, weights, max_batch=B, max_len=L + STEPS)
    eng.warmup(L)
    logits, cache = eng._prefill(weights, {"tokens": jnp.asarray(toks[:, :L])})
    out = [np.asarray(logits[:, -1], np.float32)]
    for i in range(STEPS):
        logits, cache = eng.decode(cache, jnp.asarray(toks[:, L + i:L + i + 1]), L + i)
        out.append(np.asarray(logits[:, -1], np.float32))
    return np.stack(out, 1)


@pytest.mark.parametrize("name", ["qwen3-4b", "glm4-9b-20l"])
def test_reference_matches_program_in_float32(name):
    cfg, weights, ref, c = _program_and_reference(name, "float32")
    toks = np.random.default_rng(3).integers(0, c["vocab_size"], (B, L + STEPS))
    got = _program_logits(cfg, weights, toks)
    want = ref.logits(weights, toks, list(range(L - 1, L + STEPS)))
    assert np.isfinite(want).all() and want.std() > 0.3
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("name", ["qwen3-4b", "glm4-9b-20l"])
def test_bf16_program_within_rounding_of_reference(name):
    """The served dtype departs from the float32 reference by rounding only:
    far less than the spread of the logits, and less than float8 does."""
    cfg, weights, ref, c = _program_and_reference(name, "bfloat16")
    toks = np.random.default_rng(4).integers(0, c["vocab_size"], (B, L + STEPS))
    got = _program_logits(cfg, weights, toks)
    pos = list(range(L - 1, L + STEPS))
    want = ref.logits(weights, toks, pos)
    low = type(ref)(c, fp8=True).logits(weights, toks, pos)
    err, err8 = np.abs(got - want).max(), np.abs(low - want).max()
    assert err < 0.1 * want.std()
    assert err8 > 2 * err


@pytest.mark.parametrize("name", ["qwen3-4b", "glm4-9b-20l"])
def test_reference_sees_every_published_part(name):
    """Norm scales, QKV biases and QK-norm weights change the reference's
    logits: the weights the benchmark makes leave no part at its identity."""
    _, weights, ref, c = _program_and_reference(name, "float32")
    toks = np.random.default_rng(5).integers(0, c["vocab_size"], (1, L))
    base = ref.logits(weights, toks, [L - 1])
    mixer = weights["blocks"]["pos0"]["mixer"]
    parts = [k for k in ("bq", "bv", "q_norm") if k in mixer]
    assert parts
    for k in parts:
        w2 = jax.tree.map(lambda a: a, weights)
        w2["blocks"]["pos0"]["mixer"] = dict(mixer, **{k: jnp.zeros_like(mixer[k])})
        assert np.abs(ref.logits(w2, toks, [L - 1]) - base).max() > 1e-3, k
