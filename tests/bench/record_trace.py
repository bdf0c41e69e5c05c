"""Record a trace for the tests in ``tests/bench/data``: one ``--trace 1``
run of a tiny copy of a cell (``tiny.py``) on a TPU, kept in the form
``bench.trace.load`` returns, gzipped JSON.

  python3 tests/bench/record_trace.py <cell> <out.json.gz> [<cell> <out> ...]

``<cell>`` is a tiny cell's name (``qwen3-4b.decode``, ``qwen3-4b.handoff``).
"""
from __future__ import annotations

import gzip
import json
import os
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(HERE)]
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")


def record(cell: str, out: Path, seed: int = 2**31 + 101) -> dict:
    from bench import harness, trace
    import tiny

    loaded = []
    load = trace.load

    def keep(log_dir):
        loaded.append(load(log_dir))
        return loaded[-1]

    trace.load = keep
    try:
        with tempfile.TemporaryDirectory(prefix="tiny-root-") as d:
            bench = harness.Bench(tiny.tiny_root(Path(d)))
            args = harness.parse_args(["--workload", cell, "--seed", str(seed),
                                       "--seconds", "2", "--trace", "1"])
            result = harness.run(args, time.monotonic(), bench=bench)
    finally:
        trace.load = load
    with gzip.open(out, "wt") as f:
        json.dump(loaded[-1], f)
    return result


def main(argv) -> int:
    if not argv or len(argv) % 2:
        print(__doc__, file=sys.stderr)
        return 2
    for cell, out in zip(argv[::2], argv[1::2]):
        result = record(cell, Path(out))
        print(json.dumps({"cell": cell, "out": out, "correct": result["correct"],
                          "metrics": result["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
