"""A whole run of the harness on the CPU at smoke widths, with the chip check
skipped, sound and with the timed path broken underneath: every fault a
serving cell can have, and the float8 control put in the served path's place,
must turn ``correct`` false."""
from __future__ import annotations

import json
import sys
import time
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(Path(__file__).resolve().parent)]

from bench import check, harness  # noqa: E402
import tiny  # noqa: E402
from repro.serving.engine import ServeEngine  # noqa: E402

CELLS = ["qwen3-4b.decode", "qwen3-4b.handoff", "glm4-9b-20l.decode",
         "glm4-9b-20l.handoff"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.tiny_root(tmp_path_factory.mktemp("bench"))


@pytest.fixture(scope="module")
def compile_cache(tmp_path_factory):
    """One persistent compilation cache for this file's runs, so each cell
    compiles once; the worker's JAX settings are put back afterwards."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs")
    before = {k: getattr(jax.config, k) for k in keys}
    path = tmp_path_factory.mktemp("jax_cache")
    jax.config.update("jax_compilation_cache_dir", str(path))
    yield path
    for k, v in before.items():
        jax.config.update(k, v)
    cc.reset_cache()


@pytest.fixture(autouse=True)
def no_chip(monkeypatch, compile_cache):
    monkeypatch.setattr(harness, "require_accelerator",
                        lambda chips: jax.devices()[:chips])
    row = harness.peak_row("TPU v5 lite")
    monkeypatch.setattr(harness, "peak_row", lambda kind: row)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(compile_cache))


def _run(root, cell, seed=2**31 + 17, seconds=0.3):
    args = harness.parse_args(["--workload", cell, "--seed", str(seed),
                               "--seconds", str(seconds), "--trace", "0"])
    out = harness.run(args, time.monotonic(), bench=harness.Bench(root),
                      log=lambda m: None)
    json.dumps(out)
    return out


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(root, cell):
    out = _run(root, cell)
    assert out["correct"], out["checked"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checked"
    assert set(out["metrics"]) == {m["name"] for m in
                                   harness.Bench(root).end_to_end(cell)}
    assert "setup_s" in out["metrics"] and len(out["metrics"]) >= 3


def _altered_token(monkeypatch, *_):
    orig = ServeEngine.step_batch

    def step_batch(self):
        done = orig(self)
        for r in done:
            r.result[-1] = (r.result[-1] + 1) % self.cfg.vocab_size
        return done
    monkeypatch.setattr(ServeEngine, "step_batch", step_batch)


def _state_unchanged(monkeypatch, *_):
    orig = ServeEngine.decode

    def decode(self, cache, token, pos):
        logits, _ = orig(self, cache, token, pos)
        return logits, cache
    monkeypatch.setattr(ServeEngine, "decode", decode)


def _half_batch_left_out(monkeypatch, *_):
    """Half of each batch is never served: its clients get no reply."""
    orig = ServeEngine.step_batch

    def step_batch(self):
        done = orig(self)
        return done[:len(done) // 2]
    monkeypatch.setattr(ServeEngine, "step_batch", step_batch)


def _exchange_left_out(monkeypatch, *_):
    drv = harness.driver("handoff")
    orig_driver = harness.driver

    def deserialize(payload, like):
        return jax.tree.map(lambda x: np.zeros(x.shape, x.dtype), like)
    monkeypatch.setattr(drv, "deserialize", deserialize)
    monkeypatch.setattr(harness, "driver",
                        lambda name: drv if name == "handoff" else orig_driver(name))


def _wrap_cycle(monkeypatch, traffic_driver, after):
    """Every cycle of the cell's driver runs as it is, then ``after(engine,
    records)`` alters what it served."""
    drv = harness.driver(traffic_driver)
    orig_driver = harness.driver

    def cycle(engine, traffic, stream):
        recs = drv.cycle(engine, traffic, stream)
        after(engine, recs)
        return recs

    def warm(engine, traffic):
        # ``after``'s own programs compile in set-up too, so that the window
        # finishes as many requests as a sound run checks
        drv.warm(engine, traffic)
        cycle(engine, traffic, "warmup")
    wrapped = types.SimpleNamespace(warm=warm, cycle=cycle)
    monkeypatch.setattr(harness, "driver",
                        lambda name: wrapped if name == traffic_driver
                        else orig_driver(name))


def _float8_control(monkeypatch, root, cell):
    """The reference computed in float8 serves in the program's place: each
    request's tokens are its greedy tokens after the prompt."""
    bench = harness.Bench(root)
    w = bench.cell(cell)
    config = bench.config(w["config"])
    low = bench.reference(config).reference(bench.canonical(config), fp8=True)

    def after(engine, recs):
        toks = check.greedy(low, engine.params, [r.prompt for r in recs],
                            len(recs[0].tokens))
        for r, t in zip(recs, toks):
            r.tokens = t.tolist()
    _wrap_cycle(monkeypatch, bench.mix(w["traffic"])["driver"], after)


FAULTS = {"altered_token": (_altered_token, CELLS),
          # a handoff cell's one decode step feeds no later step
          "state_unchanged": (_state_unchanged,
                              ["qwen3-4b.decode", "glm4-9b-20l.decode"]),
          "half_batch_left_out": (_half_batch_left_out,
                                  ["qwen3-4b.decode", "glm4-9b-20l.decode"]),
          "exchange_left_out": (_exchange_left_out,
                                ["qwen3-4b.handoff", "glm4-9b-20l.handoff"])}


@pytest.mark.parametrize("fault,cell", [(f, c) for f, (_, cells) in FAULTS.items()
                                        for c in cells])
def test_fault_turns_correct_false(root, monkeypatch, fault, cell):
    FAULTS[fault][0](monkeypatch, root, cell)
    out = _run(root, cell)
    assert not out["correct"], out["checked"]
    gap = out["checked"]["max_gap"]
    assert out["failed"] > 0 or gap["value"] > gap["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_float8_control_fails_the_limit(root, monkeypatch, cell):
    """The reference in float8, serving in the program's place, comes out
    not correct through the harness's own decision."""
    _float8_control(monkeypatch, root, cell)
    # the control serves from the reference, more slowly than the engine: a
    # longer window finishes as many requests as a sound run checks
    out = _run(root, cell, seconds=2.0)
    assert out["failed"] == 0
    assert not out["correct"], out["checked"]
    gap = out["checked"]["max_gap"]
    assert gap["value"] > gap["limit"]
