"""Benchmark driver — one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows (benchmarks/common.emit).

  fig1        lifecycle phase breakdown (D/KVS/S3, 128 MB)
  fig7/fig8   chained workflow totals + IO-impact reduction
  fig9        chained latency vs input size (+9d improvements)
  fig10       video-analytics latency sweep (+10d)
  fig11       added-cold-start-delay sweep
  eq4         analytic-model validation (+ pipelined-transfer extension)
  stream.*    chunked-streaming sweep: blob vs stream vs dedup fan-out
  pipeline.*  function-to-function direct streaming: whole-blob chain vs
              mid-execution chunk flow (tandem floor + Eq. 4 error)
  locality.*  load-only vs digest-aware placement (fan-out + video)
  policy.*    per-edge DataPolicy plans: mixed vs best global knob;
              multi-input fan-in hints vs joined-blob hashing
  adaptive.*  telemetry-backed auto plans vs the exhaustive per-edge
              oracle and the best uniform configuration (+ Eq. 4 error)
  replan.*    mid-flight re-planning under a wave-2 link degradation:
              frozen plan vs replanned vs post-degradation oracle, plus
              speculation="auto" budget resolution
  fault.*     node crash recovery: data-plane-aware retries (re-ship from
              surviving CAS replicas) vs naive restart + full rerun
  mt.*        multi-tenant serving fleet: Eq. 5 SJF admission + plan-aware
              pre-warm + shared CAS vs a FIFO no-pool baseline
  sub.*       runtime-substrate microbenches vs the frozen pre-refactor
              hot paths (placements/sec, chunk grants/sec, bus publish +
              late-joiner reads, streamed digest MB/s)
  train.*     SDP overlap on a real-compile training cold start
  serve.*     CSP overlap on a prefill->decode KV handoff

Env: BENCH_SCALE (default 0.5) shrinks simulated time; BENCH_FAST=1 runs a
reduced grid; BENCH_SKIP=ml skips the real-compile ML benches; BENCH_JSON
sets the machine-readable output path (default BENCH_truffle.json in cwd).

``--smoke``: CI mode — forces the fast grid at a small scale, skips the
real-compile ML benches, then validates that BENCH_truffle.json was
produced and is well-formed (non-empty, numeric us_per_call). Exits
non-zero on a malformed or missing results file."""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def main() -> None:
    t0 = time.time()
    smoke = "--smoke" in sys.argv[1:]
    if smoke:   # must be set before benchmarks.common is imported
        os.environ.setdefault("BENCH_SCALE", "0.05")
        os.environ["BENCH_FAST"] = "1"
        os.environ.setdefault("BENCH_SKIP", "ml")
    fast = os.environ.get("BENCH_FAST") == "1"
    skip = set(os.environ.get("BENCH_SKIP", "").split(","))

    from benchmarks import (adaptive_sweep, chained_sweep, chained_total,
                            coldstart_sweep, fault_sweep, lifecycle,
                            locality_sweep, model_validation,
                            multitenant_sweep, pipeline_sweep, policy_sweep,
                            replan_sweep, streaming_sweep,
                            substrate_bench, video_analytics)

    print("# --- paper figures ---")
    lifecycle.run(size_mb=32 if fast else 128)
    chained_total.run(size_mb=32 if fast else 128)
    chained_sweep.run(sizes=(8, 32) if fast else (8, 32, 64, 128))
    video_analytics.run(sizes=(8, 32) if fast else (8, 32, 64, 128))
    coldstart_sweep.run(size_mb=64 if fast else coldstart_sweep.SIZE_MB,
                        delays=(0.0, 4.0) if fast else
                        (0.0, 2.0, 4.0, 6.0, 8.0, 10.0))
    model_validation.run()

    print("# --- chunked streaming data plane ---")
    streaming_sweep.run(sizes=(32,) if fast else (32, 128),
                        tiers=("edge-edge",) if fast
                        else ("edge-edge", "edge-cloud"))

    print("# --- function-to-function direct streaming (pipelined chain) ---")
    pipeline_sweep.run()

    print("# --- locality-aware placement ---")
    locality_sweep.run()

    print("# --- per-edge DataPolicy plans ---")
    policy_sweep.run()

    print("# --- adaptive planner (auto vs oracle vs uniforms) ---")
    adaptive_sweep.run()

    print("# --- mid-flight re-planning (frozen vs replanned vs oracle) ---")
    replan_sweep.run()

    print("# --- node crash recovery (replica re-ship vs naive rerun) ---")
    fault_sweep.run()

    print("# --- multi-tenant serving fleet (SJF+pools+sharing vs FIFO) ---")
    multitenant_sweep.run()

    print("# --- runtime substrate (vs frozen pre-refactor hot paths) ---")
    substrate_bench.run(fast=fast)

    if "ml" not in skip:
        print("# --- ML-framework integration (real XLA compile) ---")
        from benchmarks import serve_handoff, train_coldstart
        train_coldstart.run()
        serve_handoff.run()

    path = _dump_json(t0)
    print(f"# total benchmark wall time: {time.time() - t0:.0f}s")
    if smoke:
        _validate_json(path)


def _dump_json(t0: float) -> str:
    """Machine-readable results (per-benchmark us_per_call + parsed derived
    metrics) so the perf trajectory is trackable across PRs."""
    import json

    from benchmarks.common import EMITTED, SCALE

    path = os.environ.get("BENCH_JSON", "BENCH_truffle.json")
    doc = {"schema": 1,
           "bench_scale": SCALE,
           "wall_seconds": round(time.time() - t0, 1),
           "benchmarks": EMITTED}
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"# wrote {len(EMITTED)} benchmark rows to {path}")
    return path


def _validate_json(path: str) -> None:
    """Smoke contract: the results file exists, parses, and every row has a
    name and a numeric us_per_call. Exits non-zero otherwise."""
    import json

    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        sys.exit(f"smoke: cannot read {path}: {e}")
    problems = []
    if doc.get("schema") != 1:
        problems.append(f"unexpected schema: {doc.get('schema')!r}")
    rows = doc.get("benchmarks")
    if not isinstance(rows, list) or not rows:
        problems.append("no benchmark rows")
    else:
        for i, row in enumerate(rows):
            if not isinstance(row.get("name"), str) or not row["name"]:
                problems.append(f"row {i}: bad name {row.get('name')!r}")
            us = row.get("us_per_call")
            if not isinstance(us, (int, float)) or us != us:   # NaN check
                problems.append(f"row {i} ({row.get('name')}): "
                                f"bad us_per_call {us!r}")
    if problems:
        sys.exit("smoke: malformed " + path + "\n  " + "\n  ".join(problems))
    print(f"# smoke OK: {path} well-formed ({len(rows)} rows)")


if __name__ == "__main__":
    main()
