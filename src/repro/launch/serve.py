"""Serving driver: batched generation with the Truffle-overlapped cold start.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --requests 8 \
      [--no-smoke] [--no-truffle] [--prompt-len 16] [--max-new 8]

The engine cold start (real XLA compiles of prefill + serve_step) overlaps
with SDP prefetch of request payloads from the KVS (see
examples/serve_batch.py for the scripted walkthrough)."""
from __future__ import annotations

import argparse
import threading
import time
from dataclasses import dataclass
from typing import List

import jax
import numpy as np

from repro.configs.base import ModelConfig
from repro.configs.registry import get_config, list_archs
from repro.core.buffer import Buffer
from repro.launch.compile_cache import use_compilation_cache
from repro.models import api
from repro.runtime.clock import Clock
from repro.runtime.netsim import GBPS
from repro.serving.engine import GenRequest, ServeEngine
from repro.storage.base import StorageService


@dataclass
class ServeRun:
    engine: ServeEngine           # its stats, params and last decode state
    done: List[GenRequest]        # served requests, results filled in
    total_s: float


def serving_config(arch: str, smoke: bool) -> ModelConfig:
    """Serving holds its weights in the compute dtype: f32 master weights
    are a training concern, and qwen3-4b's (17.6 GB) would not fit the
    16 GiB of one TPU v5e."""
    cfg = get_config(arch, smoke=smoke)
    return cfg.replace(param_dtype=cfg.dtype)


def main(argv=None) -> ServeRun:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b", choices=list_archs())
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--no-smoke", dest="smoke", action="store_false")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--truffle", action="store_true", default=True)
    ap.add_argument("--no-truffle", dest="truffle", action="store_false")
    ap.add_argument("--kvs-gbps", type=float, default=0.002)
    args = ap.parse_args(argv)

    use_compilation_cache()
    cfg = serving_config(args.arch, args.smoke)
    params = api.init(cfg, jax.random.PRNGKey(0))
    engine = ServeEngine(cfg, params, max_batch=args.batch,
                         max_len=args.prompt_len + args.max_new)

    clock = Clock(1.0)
    kvs = StorageService("kvs", put_bandwidth=1 * GBPS,
                         get_bandwidth=args.kvs_gbps * GBPS, latency=0.002,
                         clock=clock)
    rng = np.random.default_rng(0)
    for i in range(args.requests):
        p = rng.integers(0, cfg.vocab_size, args.prompt_len).astype(np.int32)
        kvs.put(f"req-{i}", p.tobytes())

    buffer = Buffer(name="serve-buffer")
    t0 = time.monotonic()

    def prefetch():
        for i in range(args.requests):
            data, _ = kvs.get(f"req-{i}")
            buffer.set(f"req-{i}", data)

    if args.truffle:
        th = threading.Thread(target=prefetch, daemon=True)
        th.start()
        engine.warmup(args.prompt_len)
        th.join()
    else:
        engine.warmup(args.prompt_len)
        prefetch()

    for i in range(args.requests):
        raw = buffer.wait_for(f"req-{i}", timeout=120)
        engine.submit(GenRequest(f"req-{i}",
                                 np.frombuffer(raw, np.int32).tolist(),
                                 args.max_new))
    done: List[GenRequest] = []
    while True:
        batch = engine.step_batch()
        if not batch:
            break
        done.extend(batch)
    total = time.monotonic() - t0
    print(f"mode={'truffle' if args.truffle else 'baseline'} served={len(done)} "
          f"tokens={engine.stats.tokens_out} total={total:.2f}s "
          f"compile={engine.stats.compile_s:.2f}s")
    return ServeRun(engine, done, total)


if __name__ == "__main__":
    main()
