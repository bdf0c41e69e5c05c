"""DeepSeek-V2-Lite's chip share (``bench/configs/deepseek-v2-lite-ep4.json``)
at smoke widths on the CPU: the served path against the plain reference
``bench/reference/mla_moe.py``, the expert-parallel share against the uncut
layer, serving without drops, YaRN against DeepSeek's own definition, and the
engine's MoE counters."""
from __future__ import annotations

import json
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(Path(__file__).resolve().parent)]

from bench import harness, model  # noqa: E402
from repro.configs.base import MoEConfig  # noqa: E402
from repro.configs.registry import get_config  # noqa: E402
from repro.models import layers, moe  # noqa: E402
from repro.models.params import init_params  # noqa: E402
from repro.serving.engine import GenRequest, ServeEngine  # noqa: E402

NAME = "deepseek-v2-lite-ep4"
# Every width cut to smoke size by its published key; the structure kept: one
# leading dense layer, then two MoE layers, each holding 4 of the 16 experts
# its router scores, top-3, 2 shared; MLA without a query low rank, and YaRN
# as published.
SMOKE = {"num_hidden_layers": 3, "hidden_size": 64, "num_attention_heads": 4,
         "intermediate_size": 128, "vocab_size": 512, "kv_lora_rank": 16,
         "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
         "moe_intermediate_size": 32, "n_routed_experts": 4,
         "num_experts_per_tok": 3}
ROUTER = 16
L, STEPS, B = 10, 4, 2


def tiny_config() -> dict:
    cfg = json.loads((ROOT / "bench" / "configs" / f"{NAME}.json").read_text())
    cfg.update(SMOKE)
    cfg["bench"]["architecture"]["moe.router_experts"] = ROUTER
    return cfg


def _program_and_reference(dtype):
    config = tiny_config()
    refmod = harness.Bench().reference(config)
    c = model.canonical(config, refmod.KEYS)
    cfg = model.program_config(config, c).replace(dtype=dtype, param_dtype=dtype)
    return cfg, model.make_weights(cfg, 11), refmod, c


def _program_logits(cfg, weights, toks):
    """Prefill logits of the prompt, then one decode step per further token
    through the engine's compiled programs and grown cache: [B, STEPS + 1, V]."""
    eng = ServeEngine(cfg, weights, max_batch=B, max_len=L + STEPS)
    eng.warmup(L)
    logits, cache = eng._prefill(weights, {"tokens": jnp.asarray(toks[:, :L])})
    assert cache["lead"]["ckv"].shape == (1, B, L + STEPS, 16)
    out = [np.asarray(logits[:, -1], np.float32)]
    for i in range(STEPS):
        logits, cache = eng.decode(cache, jnp.asarray(toks[:, L + i:L + i + 1]), L + i)
        out.append(np.asarray(logits[:, -1], np.float32))
    return np.stack(out, 1)


def test_reference_matches_program_in_float32():
    """Same equations in float32 on both sides: they differ only in the order
    of sums (absorbed decode attention, grouped expert matmuls), 2e-4."""
    cfg, weights, refmod, c = _program_and_reference("float32")
    toks = np.random.default_rng(3).integers(0, c["vocab_size"], (B, L + STEPS))
    got = _program_logits(cfg, weights, toks)
    want = refmod.Reference(c).logits(weights, toks, list(range(L - 1, L + STEPS)))
    assert np.isfinite(want).all() and want.std() > 0.3
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_bf16_program_within_rounding_of_reference():
    """The served dtype departs from the float32 reference by bf16 rounding:
    under a tenth of the logits' spread, while the reference with float8
    matmul operands departs by more than twice as much, so the comparison
    tells a lower precision from the served one."""
    cfg, weights, refmod, c = _program_and_reference("bfloat16")
    toks = np.random.default_rng(4).integers(0, c["vocab_size"], (B, L + STEPS))
    got = _program_logits(cfg, weights, toks)
    pos = list(range(L - 1, L + STEPS))
    want = refmod.Reference(c).logits(weights, toks, pos)
    low = refmod.Reference(c, fp8=True).logits(weights, toks, pos)
    err, err8 = np.abs(got - want).max(), np.abs(low - want).max()
    assert err < 0.1 * want.std()
    assert err8 > 2 * err


def _layer_cfg(held, router=ROUTER, top_k=3):
    base = get_config("deepseek-v2-lite", smoke=True).replace(
        dtype="float32", param_dtype="float32")
    return base.replace(moe=MoEConfig(
        num_experts=held, router_experts=router, top_k=top_k, d_expert=32,
        num_shared=2, norm_topk_prob=False))


def test_four_shares_add_up_to_the_uncut_layer():
    """Four ranks of 4 experts each, every one routing over all 16: their
    outputs, with the shared experts (computed on every rank) counted once,
    add up to the layer that holds all 16."""
    whole = _layer_cfg(held=16)
    p = init_params(moe.moe_defs(whole), jax.random.PRNGKey(2), "float32")
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 8, whole.d_model), jnp.float32)
    want, _ = moe.moe_apply(whole, p, x, mode="decode")
    share = _layer_cfg(held=4)
    parts = []
    for r in range(4):
        held = slice(4 * r, 4 * r + 4)
        pr = dict(p, router=jnp.roll(p["router"], -4 * r, axis=1),
                  w_gate=p["w_gate"][held], w_up=p["w_up"][held],
                  w_down=p["w_down"][held])
        parts.append(moe.moe_apply(share, pr, x, mode="decode")[0])
    shared = moe._shared(p, x.reshape(-1, whole.d_model)).reshape(x.shape)
    got = sum(parts) - 3 * shared
    assert np.abs(np.asarray(parts[0] - shared)).max() > 1e-3
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_a_layer_reads_only_its_own_experts_from_the_stack():
    """Served inside the layer scan, a layer reads its held experts from the
    stack of every layer's, at its own offset: with the other layers' expert
    weights NaN, its output equals the layer's own, and nothing else is
    read (float32; the jitted and the eager call round apart by ~1e-6)."""
    cfg = _layer_cfg(held=4)
    p = init_params(moe.moe_defs(cfg), jax.random.PRNGKey(6), "float32")
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 8, cfg.d_model), jnp.float32)
    want, _ = moe.moe_apply(cfg, p, x, mode="decode")
    stack = {n: jnp.stack([jnp.full_like(p[n], jnp.nan), p[n], jnp.full_like(p[n], jnp.nan)])
             for n in moe.EXPERT_WEIGHTS}
    got, _ = jax.jit(lambda l: moe.moe_apply(cfg, p, x, mode="decode",
                                             experts=(stack, l)))(jnp.int32(1))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def _per_token(cfg, p, xt):
    """Each token alone: softmax over the router, top-k, the held experts'
    SwiGLUs weighted by their probabilities, plus the shared experts."""
    m = cfg.moe
    out = []
    for x in np.asarray(xt, np.float64):
        logits = x @ np.asarray(p["router"], np.float64)
        probs = np.exp(logits - logits.max())
        probs /= probs.sum()
        y = np.zeros_like(x)
        for e in np.argsort(-probs)[:m.top_k]:
            if e < m.num_experts:
                g = x @ np.asarray(p["w_gate"][e], np.float64)
                u = x @ np.asarray(p["w_up"][e], np.float64)
                y += probs[e] * ((g / (1 + np.exp(-g)) * u)
                                 @ np.asarray(p["w_down"][e], np.float64))
        out.append(y)
    shared = moe._shared(p, jnp.asarray(xt, jnp.float32))
    return np.stack(out) + np.asarray(shared, np.float64)


@pytest.mark.parametrize("held,block", [(4, None), (16, None), (4, 16)])
def test_rigged_router_drops_nothing(monkeypatch, held, block):
    """A router rigged so that every token picks held expert 0 among its
    top-k: the served layer equals a per-token loop, with every assignment
    served (the training path's capacity, 1.25 * k * T / E, would drop
    three quarters of them on the uncut layer); also when a long prefill runs
    in blocks."""
    if block:
        monkeypatch.setattr(moe, "DROPLESS_BLOCK", block)
    cfg = _layer_cfg(held=held)
    p = init_params(moe.moe_defs(cfg), jax.random.PRNGKey(4), "float32")
    p["router"] = p["router"].at[:, 0].set(1.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(5), (4, 16, cfg.d_model)))
    xt = x.reshape(-1, cfg.d_model)
    T = xt.shape[0]
    assert bool(jnp.all(jnp.argmax(xt @ p["router"], -1) == 0))
    got, counts = moe.moe_apply(cfg, p, x, mode="prefill")
    np.testing.assert_allclose(np.asarray(got).reshape(T, -1), _per_token(cfg, p, xt),
                               rtol=1e-4, atol=1e-4)
    top_i = np.argsort(-np.asarray(xt @ p["router"]), -1)[:, :cfg.moe.top_k]
    assert int(counts["moe_routed"]) == int((top_i < held).sum())
    assert int(counts["moe_dropped"]) == 0
    if not block:
        assert int(counts["moe_busiest"]) == T
        if held == 16:
            assert moe._capacity(cfg, T) < T


def _deepseek_yarn(dim, base, factor, orig, beta_fast, beta_slow, mscale,
                   mscale_all_dim, seq_len):
    """``DeepseekV2YarnRotaryEmbedding._set_cos_sin_cache`` and the
    attention's ``softmax_scale`` factor (modeling_deepseek.py), in numpy
    float32; returns (inv_freq, cos, sin, (low, high), softmax factor)."""
    def find_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) / (2 * math.log(base))

    def get_mscale(scale, m):
        return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0

    low = max(math.floor(find_dim(beta_fast)), 0)
    high = min(math.ceil(find_dim(beta_slow)), dim - 1)
    ar = np.arange(0, dim, 2, dtype=np.float32)
    freq_extra = np.float32(1.0) / (np.float32(base) ** (ar / dim))
    freq_inter = np.float32(1.0) / (np.float32(factor) * np.float32(base) ** (ar / dim))
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low if high != low else high + 0.001 - low), 0, 1)
    mask = np.float32(1.0) - ramp
    inv_freq = freq_inter * (1 - mask) + freq_extra * mask
    freqs = np.outer(np.arange(seq_len, dtype=np.float32), inv_freq)
    m = get_mscale(factor, mscale) / get_mscale(factor, mscale_all_dim)
    return (inv_freq, np.cos(freqs) * m, np.sin(freqs) * m, (low, high),
            get_mscale(factor, mscale_all_dim) ** 2)


def test_yarn_equals_deepseek_definition():
    """At the published values: the ramp runs from dimension 10 to 23 of 32,
    the softmax scale gains (0.1 * 0.707 * ln 40 + 1)^2 = 1.2608^2, and the
    program's and the reference's frequencies and cos/sin tables equal
    DeepSeek's. The frequencies agree to float32 rounding; angles reach
    ~4.2e3 radians at 4200 positions, where that rounding moves cos and sin
    by up to ~5e-4."""
    cfg = get_config("deepseek-v2-lite")
    y, dim, base = cfg.yarn, cfg.mla.qk_rope_head_dim, cfg.rope_theta
    S = 4200
    inv_freq, cos, sin, ramp, soft = _deepseek_yarn(
        dim, base, y.factor, y.original_max_position_embeddings, y.beta_fast,
        y.beta_slow, y.mscale, y.mscale_all_dim, S)
    assert ramp == (10, 23) == layers.yarn_ramp(y, dim, base)
    assert soft == pytest.approx(1.2608 ** 2, rel=1e-4)
    assert layers.yarn_softmax_scale(y) == pytest.approx(soft, rel=1e-12)
    gc, gs = layers.yarn_tables(jnp.arange(S), dim, base, y)
    freq = np.arctan2(np.asarray(gs)[1], np.asarray(gc)[1])     # all below pi
    np.testing.assert_allclose(freq, inv_freq, rtol=2e-6)
    np.testing.assert_allclose(np.asarray(gc), cos, atol=5e-4)
    np.testing.assert_allclose(np.asarray(gs), sin, atol=5e-4)
    config = json.loads((ROOT / "bench" / "configs" / f"{NAME}.json").read_text())
    refmod = harness.Bench().reference(config)
    c = harness.Bench().canonical(config)
    rc, rs = refmod.Reference(c)._tables(S)
    np.testing.assert_allclose(np.asarray(rc), cos, atol=5e-4)
    np.testing.assert_allclose(np.asarray(rs), sin, atol=5e-4)
    assert refmod.yarn_ramp(c) == ramp
    assert refmod.softmax_scale(c) == pytest.approx(192 ** -0.5 * soft, rel=1e-12)


def test_dense_decode_program_returns_logits_and_cache_only():
    cfg = get_config("qwen3-4b", smoke=True)
    eng = ServeEngine(cfg, model.make_weights(cfg, 1), max_batch=2, max_len=12)
    eng.warmup(8)
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                         eng._decode.out_info[1])
    out = eng._decode(eng.params, cache, jnp.zeros((2, 1), jnp.int32),
                      jnp.asarray(8, jnp.int32))
    assert len(out) == 2 and len(eng._decode.out_info) == 2


def test_engine_reads_moe_counters_once_per_batch():
    """The decode program of the MoE share returns its counters; the engine
    adds them to its stats after the batch: every assignment routed to a held
    expert is served, none dropped, and the busiest expert carries at least
    the mean load."""
    cfg, weights, _, c = _program_and_reference("bfloat16")
    eng = ServeEngine(cfg, weights, max_batch=B, max_len=L + STEPS)
    assert len(eng.stats.__dict__) and eng.stats.moe_routed == 0
    rng = np.random.default_rng(6)
    for i in range(B):
        eng.submit(GenRequest(f"r{i}", rng.integers(0, 512, L).tolist(), STEPS + 1))
    eng.step_batch()
    assert len(eng._decode.out_info) == 3 and eng._moe_counts is None
    s = eng.stats
    layers_moe = c["num_layers"] - c["leading_dense_layers"]
    assert 0 < s.moe_routed <= STEPS * layers_moe * B * c["moe.top_k"]
    assert s.moe_dropped == 0
    assert s.moe_busiest * c["moe.num_experts"] >= s.moe_routed
    assert s.moe_busiest <= s.moe_routed


# ------------------------------------------------- whole runs of the harness
CELL = f"{NAME}.decode128"
TINY_MIX = {"clients": 4, "prompt_len": 12, "new_tokens": 24, "check_requests": 4}
# The limit on the widest served-token gap at these sizes, from two readings
# (CPU, six seeds each, windows of 0.3 s and 2 s, 96 positions checked):
# sound bf16 runs read at most 0.064 logits, the float8 control at least 0.60.
TINY_LIMIT = 0.25


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("bench"))


def tiny_root(tmp: Path) -> Path:
    """A root with the one cell at smoke widths and a short mix."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["workloads"] = [dict(harness.Bench().cell(CELL), why="tiny")]
    for kind in ("configs", "mixes", "limits"):
        (tmp / "bench" / kind).mkdir(parents=True)
    for kind in ("reference", "work"):
        for f in (ROOT / "bench" / kind).glob("*.py"):
            (tmp / "bench" / kind).mkdir(parents=True, exist_ok=True)
            (tmp / "bench" / kind / f.name).write_text(f.read_text())
    (tmp / "bench" / "configs" / f"{NAME}.json").write_text(json.dumps(tiny_config()))
    mix = json.loads((ROOT / "bench" / "mixes" / "decode128.json").read_text())
    (tmp / "bench" / "mixes" / "decode128.json").write_text(json.dumps({**mix, **TINY_MIX}))
    (tmp / "bench" / "limits" / f"{CELL}.json").write_text(
        json.dumps({"max_gap": TINY_LIMIT}))
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


@pytest.fixture
def no_chip(monkeypatch, tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc

    monkeypatch.setattr(harness, "require_accelerator",
                        lambda chips: jax.devices()[:chips])
    row = harness.peak_row("TPU v5 lite")
    monkeypatch.setattr(harness, "peak_row", lambda kind: row)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    cc.reset_cache()


def _run(root, seed, seconds=0.3):
    import time

    args = harness.parse_args(["--workload", CELL, "--seed", str(seed),
                               "--seconds", str(seconds), "--trace", "0"])
    return harness.run(args, time.monotonic(), bench=harness.Bench(root),
                       log=lambda m: None)


def _serve_float8(monkeypatch, root):
    """The reference computed in float8 serves in the program's place."""
    from bench import check

    bench = harness.Bench(root)
    config = bench.config(NAME)
    low = bench.reference(config).reference(bench.canonical(config), fp8=True)
    drv = harness.driver("colocated")

    def cycle(engine, traffic, stream):
        recs = drv.cycle(engine, traffic, stream)
        toks = check.greedy(low, engine.params, [r.prompt for r in recs],
                            len(recs[0].tokens))
        for r, t in zip(recs, toks):
            r.tokens = t.tolist()
        return recs

    def warm(engine, traffic):
        drv.warm(engine, traffic)
        cycle(engine, traffic, "warmup")
    import types
    monkeypatch.setattr(harness, "driver",
                        lambda name: types.SimpleNamespace(warm=warm, cycle=cycle))


@pytest.mark.parametrize("served", ["program", "float8"])
def test_harness_run_decides_correct(root, no_chip, monkeypatch, served):
    """A whole run of the cell through the harness: the served path reads
    correct, with every attempted request finished; the float8 reference in
    its place reads not correct, by the limit on the served-token gap."""
    if served == "float8":
        _serve_float8(monkeypatch, root)
    out = _run(root, 2**31 + 29, seconds=0.3 if served == "program" else 2.0)
    assert out["failed"] == 0 and out["attempted"] > 0
    gap = out["checked"]["max_gap"]
    assert out["correct"] == (served == "program"), gap
    assert set(out["metrics"]) == {"setup_s", "tokens_per_s", "latency_p50_s",
                                   "latency_p95_s"}


def test_moe_imbalance_reader():
    """``moe_imbalance`` reads the window's counters, and nothing where the
    engine counts no expert-parallel layer (a dense cell, or a program
    without the counters)."""
    from types import SimpleNamespace

    read = harness.metric_reader("moe_imbalance").read
    c = {"moe.num_experts": 16}
    ctx = SimpleNamespace(c=c, stats={"moe_routed": 192 * 26 * 10,
                                      "moe_busiest": 24 * 26 * 10})
    assert read(ctx) == pytest.approx(2.0)
    assert read(SimpleNamespace(c=c, stats={"moe_routed": 0, "moe_busiest": 0})) is None
    assert read(SimpleNamespace(c={}, stats={"decode_steps": 5})) is None
