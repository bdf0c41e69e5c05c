"""chip_smoke.py's phases at smoke width on the CPU (the greedy-vs-prefill
and bitwise-handoff checks), its refusal of a non-TPU device, and where the
entry points put JAX's persistent compilation cache."""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def served(chip_smoke, tmp_path_factory):
    # keep this worker's global compile-cache setting untouched
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("JAX_COMPILATION_CACHE_DIR",
                  str(tmp_path_factory.mktemp("jax_cache")))
        return chip_smoke.phase_serve(smoke=True)


def test_serve_phase_matches_full_prefill(chip_smoke, served):
    assert len(served.done) == chip_smoke.REQUESTS
    assert all(len(r.prompt) == chip_smoke.PROMPT_LEN and
               len(r.result) == chip_smoke.MAX_NEW for r in served.done)
    assert served.engine.cfg.param_dtype == served.engine.cfg.dtype
    chk = chip_smoke.greedy_vs_prefill(served.engine, served.done)
    assert chk["positions"] == chip_smoke.REQUESTS * chip_smoke.MAX_NEW
    assert chk["mismatches"] <= chk["near_tie_positions"]


def test_greedy_check_rejects_wrong_tokens(chip_smoke, served):
    wrong = [type(r)(r.uid, r.prompt, r.max_new_tokens, list(r.result))
             for r in served.done]
    # an arbitrary other token sits far below the top logit: no near tie
    cfg = served.engine.cfg
    for r in wrong:
        r.result[3] = (r.result[3] + cfg.vocab_size // 2) % cfg.vocab_size
    with pytest.raises(chip_smoke.CheckFailed):
        chip_smoke.greedy_vs_prefill(served.engine, wrong)


def test_handoff_phase_is_bitwise(chip_smoke, served, capsys):
    out = chip_smoke.phase_handoff(served)
    # the cache travels in its own dtype: its bytes plus a header under 4 KiB
    raw = out["raw_bytes"]
    assert 1.0 <= out["payload_bytes"] / raw < 1.0 + 4096 / raw
    assert out["sim_s"] > 0
    assert "bitwise equal" in capsys.readouterr().out


def test_main_refuses_cpu(chip_smoke, capsys):
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "needs a TPU" in out.err


def test_script_refuses_cpu_and_prints_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, timeout=120, env=env)
    assert p.returncode != 0
    assert '"ok"' not in p.stdout


def test_compile_cache_honours_env_dir(tmp_path):
    cache = tmp_path / "cache"
    before = set(compile_cache.REPO_CACHE_DIR.glob("*"))
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.launch.compile_cache import use_compilation_cache\n"
            "print(use_compilation_cache())\n"
            "jax.jit(lambda x: x * 3 + 1)(jnp.ones(3)).block_until_ready()\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(cache),
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
           "PYTHONPATH": str(ROOT / "src")}
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120, env=env)
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stdout.strip() == str(cache)
    assert any(cache.iterdir())
    assert set(compile_cache.REPO_CACHE_DIR.glob("*")) == before


def test_compile_cache_defaults_to_fixed_checkout_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        first = compile_cache.use_compilation_cache()
        assert first == ROOT / ".jax_cache"
        assert jax.config.jax_compilation_cache_dir == str(first)
        assert compile_cache.use_compilation_cache() == first
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
