"""Run one benchmark cell on the chip and print its result as one JSON line.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cells, metrics and bounds are in BENCHMARK.json at the root of the
checkout; PERF.md says what each measures. Exits 2, printing no result, on
any device but a TPU.
"""
import time

T_START = time.monotonic()

import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# The persistent compilation cache lives at a fixed path inside the checkout,
# whatever the environment names, so that two checkouts share nothing.
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
