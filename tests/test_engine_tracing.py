"""The program's own profiler spans and counters: the serving engine's
``truffle.engine.*`` spans and decode counters, the CSP codec's
``truffle.csp.*`` spans, and the compile counts of ``launch/compile_cache``.
Span names are recorded by wrapping ``jax.profiler.TraceAnnotation``; one
test reads them back from a real profiler trace."""
import contextlib
import glob
import json
import struct

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpoint import deserialize, serialize
from repro.configs.registry import get_config
from repro.launch import compile_cache
from repro.models import api
from repro.serving.engine import GenRequest, ServeEngine

B, PROMPT, NEW = 2, 6, 5
STEP = ["truffle.engine.decode", "truffle.engine.sample", "truffle.engine.token_sync"]
BATCH = (["truffle.engine.batch", "truffle.engine.prefill",
          "truffle.engine.first_token"] + STEP * (NEW - 1))


@pytest.fixture(scope="module")
def engine():
    cfg = get_config("qwen3-4b", smoke=True)
    eng = ServeEngine(cfg, api.init(cfg, jax.random.PRNGKey(0)), max_batch=B,
                      max_len=PROMPT + NEW)
    eng.warmup(PROMPT)
    return eng


@pytest.fixture
def spans(monkeypatch):
    """Names of the ``truffle.*`` annotations opened, in order."""
    names = []
    real = jax.profiler.TraceAnnotation

    @contextlib.contextmanager
    def annotation(name, **kw):
        if name.startswith("truffle."):
            names.append(name)
        with real(name, **kw):
            yield
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", annotation)
    return names


def _serve(eng, n_batches=1):
    for b in range(n_batches):
        for i in range(B):
            eng.submit(GenRequest(f"r{b}.{i}", list(range(1 + i, 1 + i + PROMPT)),
                                  NEW))
        assert len(eng.step_batch()) == B


def test_step_batch_spans_in_order(engine, spans):
    _serve(engine)
    assert spans == BATCH


def test_first_batch_warms_up_inside_its_batch_span(spans):
    cfg = get_config("qwen3-4b", smoke=True)
    eng = ServeEngine(cfg, api.init(cfg, jax.random.PRNGKey(1)), max_batch=B,
                      max_len=PROMPT + NEW)
    _serve(eng)
    assert spans == BATCH[:1] + ["truffle.engine.warmup"] + BATCH[1:]
    assert eng.stats.compile_s > 0


def test_decode_counters(engine):
    before = dict(vars(engine.stats))
    _serve(engine, n_batches=2)
    s = engine.stats
    assert s.decode_steps - before["decode_steps"] == 2 * (NEW - 1)
    host = s.decode_host_s - before["decode_host_s"]
    assert 0 < host < s.decode_s - before["decode_s"]


def test_handoff_decode_has_its_span(engine, spans):
    _serve(engine)
    state = engine.last_state
    del spans[:]
    engine.decode(state.cache, state.token, state.pos)
    assert spans == ["truffle.engine.decode"]


def _tree():
    rng = np.random.default_rng(0)
    return {"k": jnp.asarray(rng.normal(size=(2, 3, 4)), jnp.bfloat16),
            "n": jnp.arange(6, dtype=jnp.int32),
            "v": jnp.asarray(rng.normal(size=(2, 3, 4)), jnp.bfloat16)}


def _layout(leaves):
    """The documented CSP layout of ``[(key, host array), ...]``."""
    pad = lambda n: bytes(-n % 64)
    entries, data = [], b""
    for key, v in leaves:
        entries.append([key, str(v.dtype), list(v.shape), len(data)])
        data += v.tobytes() + pad(v.nbytes)
    header = json.dumps({"leaves": entries}).encode()
    head = b"TRFCSP01" + struct.pack("<Q", len(header)) + header
    return head + pad(len(head)) + data


def test_serialize_spans_and_payload(spans):
    tree = _tree()
    payload = serialize(tree)
    assert spans == ["truffle.csp.serialize",
                     "truffle.csp.pack",
                     "truffle.csp.device_pack",                   # k, n, v at once
                     "truffle.csp.d2h"]
    assert payload == _layout([(k, np.asarray(v)) for k, v in tree.items()])

    del spans[:]
    back = deserialize(payload, like=tree)
    assert spans == ["truffle.csp.deserialize", "truffle.csp.unpack"]
    for key, leaf in tree.items():
        assert back[key].dtype == leaf.dtype
        np.testing.assert_array_equal(back[key], np.asarray(leaf))


def test_spans_land_on_the_profiler_host_plane(engine, tmp_path):
    """Under a profiler session the spans are host events on the trace's
    clock, and nest: every per-step span lies inside its batch."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve(engine)
        serialize(engine.last_state.cache)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for plane in jax.profiler.ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name.startswith("truffle.")]
    names = [n for n, _, _ in events]
    for name in set(BATCH) | {"truffle.csp.serialize", "truffle.csp.d2h",
                              "truffle.csp.pack", "truffle.csp.device_pack"}:
        assert name in names, name
    (_, b0, b1), = [e for e in events if e[0] == "truffle.engine.batch"]
    steps = [e for e in events if e[0] in STEP]
    assert len(steps) == len(STEP) * (NEW - 1)
    assert all(b0 <= s and e <= b1 for _, s, e in steps)


def test_compile_snapshot_counts_a_miss_then_a_hit(tmp_path, monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache as cc

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.use_compilation_cache() == tmp_path
    keys = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    before = {k: getattr(jax.config, k) for k in keys}
    x = jnp.arange(7.0)
    f = jax.jit(lambda a: a * 7.0 - 3.0)
    try:
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        cc.reset_cache()
        s0 = compile_cache.compile_snapshot()
        f(x).block_until_ready()
        s1 = compile_cache.compile_snapshot()
        jax.clear_caches()
        f(x).block_until_ready()
        s2 = compile_cache.compile_snapshot()
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        cc.reset_cache()
    first = {k: s1[k] - s0[k] for k in s0}
    second = {k: s2[k] - s1[k] for k in s0}
    assert (first["cache_misses"], first["cache_hits"],
            first["backend_compiles"]) == (1, 0, 1)
    assert (second["cache_misses"], second["cache_hits"],
            second["backend_compiles"]) == (0, 1, 1)
    assert first["backend_compile_s"] > 0
