"""The benchmark harness: one cell, one seed, one process.

Everything that belongs to one configuration, one traffic mix or one metric
is a file of its own, found by the name ``BENCHMARK.json`` gives it:

  bench/configs/<config>.json    published config.json keys, as run
  bench/mixes/<traffic>.json     a traffic mix: its driver and parameters
  bench/drivers/<driver>.py      how a mix's requests reach the engine
  bench/metrics/<metric>.py      a per-layer metric's reader
  bench/limits/<cell>.json       the limit the correctness check holds
  bench/reference/<name>.py      a configuration's plain reference, and the
                                 sizes it takes (``KEYS``)
  bench/work/<name>.py           the operations and bytes of its prefill and
                                 decode steps, under the reference's name

A run: refuse anything but a TPU; make the weights from the seed; build the
engine through the program's own path and warm the cell's shapes (set-up);
with ``--trace 1``, trace a few cycles of the traffic; measure for
``--seconds``; read the device's peak memory; free the served state; check a
sample of the finished requests against the reference; print one JSON line.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType, SimpleNamespace
from typing import Any, Dict, List, Optional

import numpy as np

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class NoAccelerator(RuntimeError):
    pass


# --------------------------------------------------------------- resolution
def _load_module(kind: str, name: str, base: Path = BENCH_DIR) -> ModuleType:
    path = base / kind / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no {kind.rstrip('s')} {name!r} (looked for {path})")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(name: str) -> ModuleType:
    return _load_module("drivers", name)


def quantity(name: str) -> str:
    """The quantity a metric's name measures: the part before its first dot.
    ``latency_p50_s.handoff`` is ``latency_p50_s`` in the cells that list it,
    with a bound of its own; ``mfu.handoff`` is ``mfu`` moving another
    end-to-end metric than ``mfu.decode``."""
    return name.split(".", 1)[0]


def metric_reader(name: str) -> ModuleType:
    """``bench/metrics/<name>.py``, or where there is none, the reader of the
    name's quantity (``mfu.py`` for ``mfu.decode``)."""
    if not (BENCH_DIR / "metrics" / f"{name}.py").is_file():
        return _load_module("metrics", quantity(name))
    return _load_module("metrics", name)


class Bench:
    """``BENCHMARK.json`` and the data files it names, under ``root``."""

    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    def _data(self, kind: str, name: str) -> Dict[str, Any]:
        path = self.root / "bench" / kind / f"{name}.json"
        if not path.is_file():
            raise KeyError(f"no {kind[:-1]} {name!r} (looked for {path})")
        return json.loads(path.read_text())

    def cell(self, name: str) -> Dict[str, Any]:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r}; have "
                       f"{[w['name'] for w in self.spec['workloads']]}")

    def config(self, name: str) -> Dict[str, Any]:
        return self._data("configs", name)

    def mix(self, name: str) -> Dict[str, Any]:
        return self._data("mixes", name)

    def limit(self, cell: str) -> Dict[str, Any]:
        return self._data("limits", cell)

    def reference(self, config: Dict[str, Any]) -> ModuleType:
        """The configuration's plain reference, ``bench/reference/<name>.py``."""
        return _load_module("reference", config["bench"]["reference"],
                            self.root / "bench")

    def work(self, config: Dict[str, Any]) -> ModuleType:
        """The configuration's work count, ``bench/work/<name>.py`` under its
        reference's name."""
        return _load_module("work", config["bench"]["reference"],
                            self.root / "bench")

    def canonical(self, config: Dict[str, Any]) -> Dict[str, Any]:
        """The sizes the configuration states, checked against its
        reference's ``KEYS`` (``bench.model.canonical``)."""
        from bench import model

        return model.canonical(config, self.reference(config).KEYS)

    def end_to_end(self, cell: str) -> List[Dict[str, Any]]:
        return [m for m in self.spec["end_to_end"]
                if "workloads" not in m or cell in m["workloads"]]

    def per_layer(self, cell: str) -> List[Dict[str, Any]]:
        e2e = {m["name"] for m in self.end_to_end(cell)}
        return [m for m in self.spec["per_layer"]
                if (cell in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]


# ------------------------------------------------------------------ traffic
class Traffic:
    """Prompts of the mix's one length, random token ids from the seed; the
    warm-up draws from a stream of its own so the window's prompts are the
    same whether or not a run traces."""

    def __init__(self, mix: Dict[str, Any], seed: int, vocab: int):
        self.mix = mix
        self.vocab = vocab
        self._streams = {k: np.random.default_rng([seed, i])
                         for i, k in enumerate(("window", "warmup", "trace"))}
        self._n = 0

    def prompt(self, stream: str = "window") -> List[int]:
        rng = self._streams[stream]
        return rng.integers(0, self.vocab, self.mix["prompt_len"]).tolist()

    def uid(self) -> str:
        self._n += 1
        return f"r{self._n}"


@dataclass
class Record:
    """One request as its client saw it."""
    prompt: List[int]
    tokens: List[int]
    t_submit: float
    t_done: float
    handoff_s: Optional[float] = None
    payload_bytes: Optional[int] = None


@dataclass
class Window:
    records: List[Record] = field(default_factory=list)
    cycles: int = 0
    t0: float = 0.0
    t1: float = 0.0
    attempted: int = 0

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def run_cycles(drv: ModuleType, engine, traffic: Traffic, *, seconds: float = 0.0,
               cycles: int = 0, stream: str = "window", span: str = "bench.cycle"
               ) -> Window:
    """Closed-loop cycles until ``seconds`` have passed (the cycle in flight
    at the close runs to its end, and the window ends with it) or, if
    ``cycles`` is given, that many."""
    import jax

    w = Window(t0=time.monotonic())
    deadline = w.t0 + seconds
    while (w.cycles < cycles) if cycles else (time.monotonic() < deadline):
        w.attempted += traffic.mix["clients"]
        with jax.profiler.TraceAnnotation(span):
            w.records.extend(drv.cycle(engine, traffic, stream))
        w.cycles += 1
    w.t1 = time.monotonic()
    return w


# ------------------------------------------------------------------- device
def require_accelerator(chips: int):
    """The first ``chips`` TPU devices; anything else is refused."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"needs a TPU; JAX's first device is "
                            f"{devs[0].platform} ({devs[0].device_kind})")
    if len(devs) < chips:
        raise NoAccelerator(f"cell needs {chips} chips, JAX has {len(devs)}")
    return devs[:chips]


def peak_row(kind: str) -> Dict[str, Any]:
    peaks = json.loads((BENCH_DIR / "peaks.json").read_text())
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return peaks[kind]


def memory_peak_bytes(devs) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# --------------------------------------------------------------------- work
def cycle_work(work: ModuleType, c: Dict[str, Any], mix: Dict[str, Any]):
    """(prefill FLOPs, bytes), [(decode FLOPs, bytes) per step] of one cycle
    by the configuration's work count ``work``: one prefill of the batch,
    then new_tokens - 1 decode steps."""
    B, L, n = mix["clients"], mix["prompt_len"], mix["new_tokens"]
    return (work.prefill(c, B, L),
            [work.decode_step(c, B, L + i + 1) for i in range(n - 1)])


def end_to_end(window: Window, setup_s: float) -> Dict[str, float]:
    lat = [r.t_done - r.t_submit for r in window.records]
    tokens = sum(len(r.tokens) for r in window.records)
    return {"setup_s": setup_s,
            "tokens_per_s": tokens / window.seconds,
            "latency_p50_s": float(np.percentile(lat, 50)),
            "latency_p95_s": float(np.percentile(lat, 95))}


def _trace_cycles(drv, engine, traffic, n: int):
    """Run ``n`` cycles under the profiler; return the trace summary."""
    import jax

    from bench import trace

    with tempfile.TemporaryDirectory(prefix="bench-trace-") as d:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            w = run_cycles(drv, engine, traffic, cycles=n, stream="trace",
                           span=trace.WINDOW_SPAN)
        finally:
            jax.profiler.stop_trace()
        return trace.reduce(trace.load(d)), w


# ---------------------------------------------------------------------- run
def run(args: argparse.Namespace, t_start: float, bench: Optional[Bench] = None,
        log=lambda m: print(m, file=sys.stderr, flush=True)) -> Dict[str, Any]:
    """One run of one cell; returns the result line's object."""
    bench = bench or Bench()
    cell = bench.cell(args.workload)
    config = bench.config(cell["config"])
    mix = bench.mix(cell["traffic"])
    limit = bench.limit(cell["name"])
    drv = driver(mix["driver"])
    readers = {m["name"]: (m, metric_reader(m["name"]))
               for m in bench.per_layer(cell["name"])} if args.trace else {}

    marks = [("start", t_start)]
    import jax

    from bench import check, model
    from repro.launch.compile_cache import compile_snapshot, use_compilation_cache
    from repro.serving.engine import ServeEngine
    marks.append(("import", time.monotonic()))

    devs = require_accelerator(cell["chips"])
    marks.append(("devices", time.monotonic()))
    cache_dir = use_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    kind = devs[0].device_kind
    peak = peak_row(kind)
    refmod = bench.reference(config)
    c = model.canonical(config, refmod.KEYS)
    cfg = model.program_config(config, c)
    weights = jax.block_until_ready(model.make_weights(cfg, args.seed))
    marks.append(("weights", time.monotonic()))
    engine = ServeEngine(cfg, weights, max_batch=mix["clients"],
                         max_len=mix["prompt_len"] + mix["new_tokens"])
    traffic = Traffic(mix, args.seed, c["vocab_size"])
    drv.warm(engine, traffic)
    marks.append(("warm", time.monotonic()))
    setup_s = time.monotonic() - t_start
    setup = {"compile_s": engine.stats.compile_s}
    phases = " ".join(f"{n}={t - marks[i][1]:.3f}"
                      for i, (n, t) in enumerate(marks[1:]))
    compiles = compile_snapshot()
    log(f"bench: {cell['name']} seed={args.seed} set-up {setup_s:.3f} s: {phases} "
        f"(engine compile {engine.stats.compile_s:.3f}; compile cache {cache_dir}: "
        f"{_counts(compiles)})")

    summary = None
    if args.trace:
        summary, _ = _trace_cycles(drv, engine, traffic, mix["trace_cycles"])
    s0, c0 = _stats(engine), compile_snapshot()
    window = run_cycles(drv, engine, traffic, seconds=args.seconds)
    s1, c1 = _stats(engine), compile_snapshot()
    mem = memory_peak_bytes(devs)
    log(f"bench: window {window.seconds:.3f} s, {window.cycles} cycles, "
        f"{len(window.records)} requests; in the window "
        f"{_counts({k: c1[k] - c0[k] for k in c1})}")

    engine.last_state = None                 # free the served state
    ref = refmod.reference(c)
    picked = check.sample([check.Served(r.prompt, r.tokens) for r in window.records],
                          mix["check_requests"], args.seed)
    gaps = check.served_gaps(ref, weights, picked) if picked else np.array([np.inf])
    max_gap = float(gaps.max())
    failed = window.attempted - len(window.records)
    correct = bool(np.isfinite(max_gap) and max_gap <= limit["max_gap"]
                   and failed == 0)

    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    if args.trace:
        metrics = _per_layer(readers, SimpleNamespace(
            cell=cell, mix=mix, c=c, peak=peak, window=window, trace=summary,
            stats={k: s1[k] - s0[k] for k in s0}, setup=setup,
            memory_peak_bytes=mem, work=cycle_work(bench.work(config), c, mix)))
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
    else:
        values = end_to_end(window, setup_s)
        log("bench: " + " ".join(f"{k}={v!r}" for k, v in values.items()))
        metrics = {m["name"]: {"value": values[quantity(m["name"])], "unit": m["unit"]}
                   for m in bench.end_to_end(cell["name"])}
    out = {"correct": correct, "attempted": window.attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if args.trace:
        out["breakdown"] = {"device_ops": [list(x) for x in summary.device_ops],
                            "idle_gaps": [list(x) for x in summary.idle_gaps]}
    out["checked"] = {"max_gap": {"value": max_gap, "limit": limit["max_gap"]}}
    log(f"bench: checked {len(picked)} requests, {gaps.size} served tokens")
    log(f"check max_gap {max_gap!r} limit {limit['max_gap']!r}")
    return out


def _stats(engine) -> Dict[str, float]:
    """Every numeric field of the engine's ``EngineStats``."""
    s = dataclasses.asdict(engine.stats)
    return {k: v for k, v in s.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


def _counts(compiles: Dict[str, float]) -> str:
    return " ".join(f"{k}={v:g}" for k, v in compiles.items())


def _per_layer(readers, ctx) -> Dict[str, Dict[str, Any]]:
    out = {}
    for name, (spec, mod) in readers.items():
        value = mod.read(ctx)
        if value is not None:
            out[name] = {"value": float(value), "unit": spec["unit"]}
    return out


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.monotonic() if t_start is None else t_start
    args = parse_args(argv)
    try:
        out = run(args, t_start)
    except NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0
