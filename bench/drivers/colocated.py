"""Colocated serving: each cycle, every client submits its next prompt and
the engine serves them as one batch (``ServeEngine.submit``, ``step_batch``):
one prefill, then greedy decode through the grown cache on the same chip."""
from __future__ import annotations

import time
from typing import List

import jax

from bench.harness import Record, Traffic
from repro.serving.engine import GenRequest


def warm(engine, traffic: Traffic) -> None:
    """Compile the cell's prefill and decode programs and run one prefill
    and one decode step through ``step_batch``, which also compiles the
    eager ops around them."""
    mix = traffic.mix
    engine.warmup(mix["prompt_len"])
    for _ in range(mix["clients"]):
        engine.submit(GenRequest(traffic.uid(), traffic.prompt("warmup"), 2))
    engine.step_batch()


def cycle(engine, traffic: Traffic, stream: str) -> List[Record]:
    mix = traffic.mix
    reqs = [GenRequest(traffic.uid(), traffic.prompt(stream), mix["new_tokens"])
            for _ in range(mix["clients"])]
    t0 = time.monotonic()
    for r in reqs:
        engine.submit(r)
    with jax.profiler.TraceAnnotation("bench.step_batch"):
        done = engine.step_batch()
    t1 = time.monotonic()
    return [Record(r.prompt, r.result, t0, t1) for r in done]
