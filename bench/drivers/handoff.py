"""Disaggregated prefill -> decode with the KV cache as Truffle's CSP payload.

Each cycle: the prefill side serves the clients' prompts with one token
(``step_batch``, ``max_new_tokens=1``); its grown cache
(``engine.last_state``) is serialized (``checkpoint.serialize``), sent
through ``netsim.Channel`` on ``Clock(0)``, which does the channel's real host
work and sleeps for nothing, deserialized and put back on the device; the
decode side then emits the remaining tokens with ``ServeEngine.decode`` from
the restored cache."""
from __future__ import annotations

import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import Record, Traffic
from repro.checkpoint.checkpoint import deserialize, serialize
from repro.runtime.clock import Clock
from repro.runtime.netsim import GBPS, Channel
from repro.serving.engine import GenRequest


def _link(mix) -> Channel:
    return Channel("prefill->decode", mix["link_gbps"] * GBPS,
                   mix["link_latency_s"], Clock(0.0))


def warm(engine, traffic: Traffic) -> None:
    engine.warmup(traffic.mix["prompt_len"])
    cycle(engine, traffic, "warmup")


def cycle(engine, traffic: Traffic, stream: str) -> List[Record]:
    mix = traffic.mix
    reqs = [GenRequest(traffic.uid(), traffic.prompt(stream), 1)
            for _ in range(mix["clients"])]
    t0 = time.monotonic()
    for r in reqs:
        engine.submit(r)
    with jax.profiler.TraceAnnotation("bench.step_batch"):
        done = engine.step_batch()
    state = engine.last_state
    engine.last_state = None
    th = time.monotonic()
    with jax.profiler.TraceAnnotation("bench.serialize"):
        payload = serialize(state.cache)
    with jax.profiler.TraceAnnotation("bench.transfer"):
        _link(mix).transfer(payload)
    with jax.profiler.TraceAnnotation("bench.deserialize"):
        host = deserialize(payload, like=state.cache)
    with jax.profiler.TraceAnnotation("bench.device_put"):
        cache = jax.block_until_ready(jax.device_put(host))
    handoff_s = time.monotonic() - th
    del state.cache, host
    token, pos = state.token, state.pos
    out = [[r.result[0]] for r in done]
    with jax.profiler.TraceAnnotation("bench.decode"):
        for _ in range(mix["new_tokens"] - 1):
            logits, cache = engine.decode(cache, token, pos)
            nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
            token = nxt[:, None]
            for o, t in zip(out, np.asarray(nxt).tolist()):
                o.append(t)
            pos += 1
    t1 = time.monotonic()
    return [Record(r.prompt, o, t0, t1, handoff_s, len(payload))
            for r, o in zip(done, out)]
