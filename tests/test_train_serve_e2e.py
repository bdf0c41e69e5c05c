"""End-to-end integration: the training driver (with failure injection +
checkpoint/restart + truffle overlap) and the batched serving engine."""
import jax
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.launch import train
from repro.models import api
from repro.serving.engine import GenRequest, ServeEngine


def _private_compile_cache(monkeypatch, tmp_path):
    """train.main picks the compile cache; with the variable set it leaves
    this worker's JAX config (and the checkout's cache directory) alone."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))


@pytest.mark.slow
def test_train_failure_restart_resume(tmp_path, monkeypatch):
    _private_compile_cache(monkeypatch, tmp_path)
    out = train.main([
        "--arch", "qwen3-4b", "--steps", "8", "--batch", "2", "--seq", "32",
        "--ckpt-every", "3", "--inject-failure", "4",
        "--ckpt-dir", str(tmp_path), "--log-every", "100",
        "--provision-s", "0.05",
    ])
    assert out["incarnation"] == 1                  # restarted exactly once
    assert len(out["losses"]) >= 4                  # resumed from step 3 ckpt
    assert np.isfinite(out["losses"]).all()


@pytest.mark.slow
def test_train_loss_decreases(tmp_path, monkeypatch):
    _private_compile_cache(monkeypatch, tmp_path)
    out = train.main([
        "--arch", "xlstm-125m", "--steps", "15", "--batch", "4", "--seq", "32",
        "--ckpt-dir", str(tmp_path), "--log-every", "100", "--lr", "3e-3",
        "--provision-s", "0.0",
    ])
    # synthetic uniform tokens: loss should move toward ln(V) from above
    assert out["losses"][-1] <= out["losses"][0] + 0.05


def test_serving_engine_batch():
    cfg = get_config("qwen3-4b", smoke=True)
    params = api.init(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, max_batch=2, max_len=24)
    eng.submit(GenRequest("r1", [1, 2, 3, 4], max_new_tokens=4))
    eng.submit(GenRequest("r2", [5, 6, 7, 8], max_new_tokens=4))
    done = eng.step_batch()
    assert len(done) == 2
    for r in done:
        assert len(r.result) == 4
        assert all(0 <= t < cfg.vocab_size for t in r.result)
    assert eng.stats.tokens_out == 8
    assert eng.step_batch() == []           # queue drained


@pytest.mark.parametrize("arch", ["jamba-v0.1-52b", "xlstm-125m", "minicpm3-4b"])
def test_serving_engine_cache_has_the_decode_layout(arch):
    """The engine grows a hybrid, recurrent or latent prefill cache inside
    its prefill program to the decode program's ``api.cache_sds``."""
    cfg = get_config(arch, smoke=True)
    params = api.init(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, max_batch=2, max_len=12)
    eng.submit(GenRequest("r1", [1, 2, 3, 4, 5, 6], max_new_tokens=3))
    eng.submit(GenRequest("r2", [7, 8, 9, 10, 11, 12], max_new_tokens=3))
    assert [len(r.result) for r in eng.step_batch()] == [3, 3]
    want = api.cache_sds(cfg, 2, 12)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), eng.last_state.cache) == \
        jax.tree.map(lambda s: (s.shape, s.dtype), want)
    assert eng.last_state.pos == 8


def test_serving_engine_greedy_deterministic():
    cfg = get_config("qwen3-4b", smoke=True)
    params = api.init(cfg, jax.random.PRNGKey(0))
    outs = []
    for _ in range(2):
        eng = ServeEngine(cfg, params, max_batch=1, max_len=16)
        eng.submit(GenRequest("r", [1, 2, 3, 4, 5, 6, 7, 8], max_new_tokens=4))
        outs.append(eng.step_batch()[0].result)
    assert outs[0] == outs[1]


def test_serving_engine_rejects_prompt_length_it_was_not_compiled_for():
    cfg = get_config("qwen3-4b", smoke=True)
    params = api.init(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, max_batch=1, max_len=16)
    eng.warmup(8)
    eng.submit(GenRequest("r", [1, 2, 3, 4], max_new_tokens=4))
    with pytest.raises(ValueError, match="prompt length 8"):
        eng.step_batch()
