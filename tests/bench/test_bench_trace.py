"""The reduction from trace to metrics, on a synthetic trace with known
answers and on traces recorded on a TPU v5e by ``record_trace.py``: one
``--trace 1`` run of the tiny copy (``tiny.py``) of a decode cell and of a
handoff cell, in ``bench.trace.load``'s form, with the program's ``truffle.*``
spans."""
from __future__ import annotations

import gzip
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from bench import harness, trace  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
RECORDED = {"qwen3-4b.decode": DATA / "tiny_trace_v5e.json.gz",
            "qwen3-4b.handoff": DATA / "tiny_handoff_trace_v5e.json.gz"}


def _synthetic():
    ms = 1_000_000
    return {"devices": [{"name": "/device:TPU:0",
                         "ops": [["a", 0, 10 * ms], ["b", 5 * ms, 10 * ms],
                                 ["a", 30 * ms, 10 * ms], ["c", 90 * ms, 20 * ms]],
                         "modules": [["jit_prefill", 0, 15 * ms],
                                     ["jit__lambda_", 30 * ms, 10 * ms],
                                     ["jit__lambda_", 90 * ms, 20 * ms]]}],
            "host": [["bench.traced", 0, 100 * ms],
                     ["bench.cycle", 0, 100 * ms],
                     ["bench.serialize", 45 * ms, 30 * ms]]}


def test_synthetic_reduction():
    s = trace.reduce(_synthetic())
    assert s.window_s == pytest.approx(0.1)
    # ops clipped to the window: [0,15] + [30,40] + [90,100] = 35 ms busy
    assert s.busy_s == pytest.approx(0.035)
    assert s.module_time("jit_prefill") == (pytest.approx(0.015), 1)
    assert s.module_time("jit__lambda")[1] == 2
    assert dict(s.device_ops)["a"] == pytest.approx(0.02)
    gaps = dict(s.idle_gaps)
    # gaps [15,30] and [40,90]; serialize [45,75] takes its share of the second
    assert gaps["bench.serialize"] == pytest.approx(0.03)
    assert gaps["bench.cycle"] == pytest.approx(0.035)
    assert sum(gaps.values()) == pytest.approx(s.window_s - s.busy_s)


def test_reduction_needs_its_span_and_device():
    t = _synthetic()
    with pytest.raises(ValueError):
        trace.reduce(dict(t, host=[h for h in t["host"] if h[0] != "bench.traced"]))
    with pytest.raises(ValueError):
        trace.reduce(dict(t, devices=[]))


@pytest.fixture(scope="module", params=sorted(RECORDED))
def recorded(request):
    with gzip.open(RECORDED[request.param], "rt") as f:
        return request.param, json.load(f)


def test_recorded_trace(recorded):
    cell, t = recorded
    s = trace.reduce(t)
    assert 0 < s.busy_s < s.window_s
    for module in ("jit_prefill", "jit__lambda"):
        secs, calls = s.module_time(module)
        assert calls > 0 and 0 < secs < s.window_s
    assert len(s.device_ops) <= trace.TOP and len(s.idle_gaps) <= trace.TOP
    assert all(name.startswith(trace.SPAN_PREFIXES) or name.startswith("outside")
               for name, _ in s.idle_gaps)
    assert sum(t for _, t in s.idle_gaps) <= s.window_s - s.busy_s + 1e-9
    # the program's spans reach the summary, each inside the traced window
    assert {"truffle.engine.batch", "truffle.engine.prefill"} <= set(s.spans)
    if cell.endswith(".handoff"):
        assert {"truffle.csp.serialize", "truffle.csp.d2h", "truffle.csp.pack",
                "truffle.csp.unpack"} <= set(s.spans)
    for name, (secs, count) in s.spans.items():
        assert 0 < secs <= s.window_s and count > 0, name


def test_spans_are_clipped_to_the_window():
    t = _synthetic()
    t["host"] += [["truffle.csp.d2h", 40 * 1_000_000, 20 * 1_000_000],
                  ["truffle.csp.d2h", 95 * 1_000_000, 10 * 1_000_000],
                  ["truffle.csp.pack", 120 * 1_000_000, 5 * 1_000_000]]
    s = trace.reduce(t)
    assert s.spans["truffle.csp.d2h"] == (pytest.approx(0.025), 2)
    assert s.spans["bench.serialize"] == (pytest.approx(0.03), 1)
    assert "truffle.csp.pack" not in s.spans and trace.WINDOW_SPAN not in s.spans


def test_per_layer_readers_on_recorded_trace(recorded):
    """Every per-layer metric of a cell reads a number from the recorded
    trace of its tiny copy, a window and the engine's counters; shares stay
    within 0-100%."""
    cell_name, t = recorded
    bench = harness.Bench()
    cell = bench.cell(cell_name)
    config, mix = bench.config(cell["config"]), bench.mix(cell["traffic"])
    c = bench.canonical(config)
    B, n = mix["clients"], mix["new_tokens"]
    window = harness.Window(cycles=2, t0=0.0, t1=14.0, attempted=2 * B, records=[
        harness.Record([1] * mix["prompt_len"], [2] * n, 0.0, 7.0, 0.5, 1000)
        for _ in range(2 * B)])
    ctx = SimpleNamespace(cell=cell, mix=mix, c=c,
                          peak=harness.peak_row("TPU v5 lite"), window=window,
                          trace=trace.reduce(t),
                          stats={"prefill_s": 1.0, "decode_s": 12.0,
                                 "tokens_out": 2 * B * n, "decode_steps": 250,
                                 "decode_host_s": 1.25, "compile_s": 0.0},
                          setup={"compile_s": 0.3}, memory_peak_bytes=12e9,
                          work=harness.cycle_work(bench.work(config), c, mix))
    readers = {m["name"]: (m, harness.metric_reader(m["name"]))
               for m in bench.per_layer(cell["name"])}
    got = harness._per_layer(readers, ctx)
    assert set(got) == set(readers)
    for name, v in got.items():
        if v["unit"] == "%" and "roofline" not in name:
            assert 0 <= v["value"] <= 100, name
    assert got["warmup_ms"]["value"] == pytest.approx(300.0)
    spans = ctx.trace.spans
    if cell_name.endswith(".decode"):
        assert got["prefill_ms"]["value"] == pytest.approx(500.0)
        assert got["decode_step_ms"]["value"] == pytest.approx(12000.0 / (2 * (n - 1)))
        assert got["decode_host_ms"]["value"] == pytest.approx(5.0)
    else:
        handoffs = spans["truffle.csp.serialize"][1]
        assert got["handoff_d2h_ms"]["value"] == pytest.approx(
            1000 * spans["truffle.csp.d2h"][0] / handoffs)
        codec = sum(spans.get(f"truffle.csp.{k}", (0.0, 0))[0]
                    for k in ("pack", "unpack", "narrow"))
        assert got["handoff_codec_ms"]["value"] == pytest.approx(1000 * codec / handoffs)
        assert 0 < got["handoff_codec_ms"]["value"] < got["handoff_d2h_ms"]["value"] * 10


def test_readers_fall_silent_without_their_spans():
    """A trace or a window with nothing for a reader to read: the reader
    returns nothing, never 0."""
    empty = SimpleNamespace(trace=trace.reduce(_synthetic()),
                            stats={"decode_steps": 0, "decode_host_s": 0.0},
                            setup={"compile_s": 0.0})
    for name in ("handoff_d2h_ms", "handoff_codec_ms", "decode_host_ms", "warmup_ms"):
        assert harness.metric_reader(name).read(empty) is None, name
