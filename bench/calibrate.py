"""Readings a cell's correctness limit is set from, many seeds in one process.

  python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 [--control-seeds 1,2,3]

For each seed: weights and prompts from the seed, the cell's own engine and
driver at the cell's sizes, as many cycles as it takes to finish the
requests a run compares (``check_requests``), the same sample a run draws,
and the widest served-token gap against the reference (``max_gap``, the
program's reading). For a control seed, also the widest gap of the tokens the
reference computed in float8 puts first at the same positions
(``control_max_gap``), and whether the limit would pass it
(``control_correct``, which has to read false). One JSON line per seed.
Benchmark runs never run this.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control_seeds.split(",") if s}

    from bench import check, harness, model

    bench = harness.Bench()
    cell = bench.cell(args.workload)
    config, mix = bench.config(cell["config"]), bench.mix(cell["traffic"])
    limit = bench.limit(cell["name"])["max_gap"]
    devs = harness.require_accelerator(cell["chips"])
    import jax

    from repro.launch.compile_cache import use_compilation_cache
    from repro.serving.engine import ServeEngine

    use_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    refmod = bench.reference(config)
    c = model.canonical(config, refmod.KEYS)
    cfg = model.program_config(config, c)
    drv = harness.driver(mix["driver"])
    ref, low = refmod.reference(c), refmod.reference(c, fp8=True)
    cycles = math.ceil(mix["check_requests"] / mix["clients"])
    engine = None
    for seed in seeds:
        if engine is not None:
            engine.params = None
        weights = jax.block_until_ready(model.make_weights(cfg, seed))
        if engine is None:
            engine = ServeEngine(cfg, weights, max_batch=mix["clients"],
                                 max_len=mix["prompt_len"] + mix["new_tokens"])
            drv.warm(engine, harness.Traffic(mix, seed, c["vocab_size"]))
        engine.params = weights
        traffic = harness.Traffic(mix, seed, c["vocab_size"])
        w = harness.run_cycles(drv, engine, traffic, cycles=cycles)
        engine.last_state = None
        picked = check.sample([check.Served(r.prompt, r.tokens) for r in w.records],
                              mix["check_requests"], seed)
        t = time.monotonic()
        toks, positions, served = check._rows(picked)
        logits = ref.logits(weights, toks, positions)
        gaps = check.gaps_of(logits, served)
        row = {"workload": cell["name"], "seed": seed, "limit": limit,
               "max_gap": float(gaps.max()),
               "positions": int(gaps.size),
               "mismatches": int((served != logits.argmax(-1)).sum()),
               "reference_s": time.monotonic() - t}
        if seed in control:
            t = time.monotonic()
            lowl = low.logits(weights, toks, positions)
            cg = check.gaps_of(logits, lowl.argmax(-1))
            row.update(control_max_gap=float(cg.max()),
                       control_correct=bool(cg.max() <= limit),
                       control_mismatches=int((lowl.argmax(-1) != logits.argmax(-1)).sum()),
                       control_s=time.monotonic() - t)
        row["memory_peak_bytes"] = harness.memory_peak_bytes(devs)
        print(json.dumps(row), flush=True)
        del weights, logits
    return 0


if __name__ == "__main__":
    sys.exit(main())
