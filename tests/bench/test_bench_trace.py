"""The reduction from trace to metrics, on a synthetic trace with known
answers and on a trace recorded on a TPU v5e (``data/tiny_trace_v5e.json.gz``:
two cycles of the colocated driver at smoke widths, in ``bench.trace.load``'s
form)."""
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

RECORDED = Path(__file__).resolve().parent / "data" / "tiny_trace_v5e.json.gz"


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


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED, "rt") as f:
        return json.load(f)


def test_recorded_trace(recorded):
    s = trace.reduce(recorded)
    assert 0 < s.busy_s < s.window_s
    for module in ("jit_prefill", "jit__lambda"):
        secs, calls = s.module_time(module)
        assert calls > 0 and 0 < secs < s.window_s
    assert len(s.device_ops) <= trace.TOP and len(s.idle_gaps) <= trace.TOP
    assert all(name.startswith("bench.") or name.startswith("outside")
               for name, _ in s.idle_gaps)
    assert sum(t for _, t in s.idle_gaps) <= s.window_s - s.busy_s + 1e-9


def test_per_layer_readers_on_recorded_trace(recorded):
    """Every per-layer metric of a decode cell reads a number from the
    recorded trace and a window; shares stay within 0-100%."""
    from bench import flops  # noqa: F401

    bench = harness.Bench()
    cell = bench.cell("qwen3-4b.decode")
    config, mix = bench.config(cell["config"]), bench.mix(cell["traffic"])
    from bench import model

    c = model.canonical(config)
    window = harness.Window(cycles=2, t0=0.0, t1=14.0, attempted=64, records=[
        harness.Record([1] * 256, [2] * 128, 0.0, 7.0) for _ in range(64)])
    ctx = SimpleNamespace(cell=cell, mix=mix, c=c,
                          peak=harness.peak_row("TPU v5 lite"), window=window,
                          trace=trace.reduce(recorded),
                          stats={"prefill_s": 1.0, "decode_s": 12.0,
                                 "tokens_out": 64 * 128},
                          memory_peak_bytes=12e9, work=harness.cycle_work(c, mix))
    got = harness._per_layer({m["name"]: (m, harness.metric_reader(m["name"]))
                              for m in bench.per_layer(cell["name"])}, ctx)
    assert set(got) == {m["name"] for m in bench.per_layer(cell["name"])}
    for name, v in got.items():
        if v["unit"] == "%" and "roofline" not in name:
            assert 0 <= v["value"] <= 100, name
    assert got["prefill_ms"]["value"] == pytest.approx(500.0)
    assert got["decode_step_ms"]["value"] == pytest.approx(12000.0 / (2 * 127))
