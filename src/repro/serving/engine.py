"""Batched serving engine: request queue -> padded prefill -> greedy decode.

Truffle integration: the engine's first-batch cold start (real XLA compiles
of prefill_step + serve_step) is overlapped with SDP prefetch of request
payloads from storage — the serving twin of launch/train.py."""
from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import api


@dataclass
class GenRequest:
    uid: str
    prompt: List[int]
    max_new_tokens: int = 8
    result: Optional[List[int]] = None


@dataclass
class DecodeState:
    """Where a batch's decode stopped: the grown KV cache (Truffle's CSP
    payload in a prefill->decode handoff), the next input token [B, 1] and
    the cache slot it is written to."""
    cache: Any
    token: jax.Array
    pos: int


@dataclass
class EngineStats:
    compile_s: float = 0.0
    prefill_s: float = 0.0
    decode_s: float = 0.0
    # Host time in decode-loop iterations outside the token sync: the
    # previous step has finished and the next is not yet enqueued, so the
    # device mostly idles through it.
    decode_host_s: float = 0.0
    decode_steps: int = 0
    tokens_out: int = 0
    # Expert-parallel MoE layers in decode steps (``moe.MOE_COUNTERS``),
    # summed over steps and layers: top-k assignments served by held experts,
    # each step's and layer's busiest held expert's tokens, and dropped
    # assignments. Summed on the device, read once per ``step_batch``.
    moe_routed: int = 0
    moe_busiest: int = 0
    moe_dropped: int = 0


@contextmanager
def _span(name: str, stats: EngineStats, field: str):
    """A ``jax.profiler`` host span whose elapsed time is also added to
    ``stats.<field>``. With no profiler session open the span costs next to
    nothing, so it is always on."""
    with jax.profiler.TraceAnnotation(name):
        t0 = time.monotonic()
        try:
            yield
        finally:
            setattr(stats, field, getattr(stats, field) + time.monotonic() - t0)


class ServeEngine:
    """Static batcher: pad a batch of prompts, prefill once, decode greedily."""

    def __init__(self, cfg: ModelConfig, params, *, max_batch: int = 4,
                 max_len: int = 128):
        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        self._queue: List[GenRequest] = []
        self._lock = threading.Lock()
        self.stats = EngineStats()
        self.prompt_len: Optional[int] = None   # prefill length compiled for
        self.last_state: Optional[DecodeState] = None
        self._moe_counts: Optional[jax.Array] = None  # on the device, unread

    # ------------------------------------------------------------- lifecycle
    def warmup(self, prompt_len: int) -> None:
        """Cold start: trace+compile prefill and decode (call under Truffle's
        overlap window)."""
        with _span("truffle.engine.warmup", self.stats, "compile_s"):
            self._compile(prompt_len)
        self.prompt_len = prompt_len

    def _compile(self, prompt_len: int) -> None:
        cfg = self.cfg
        B, L = self.max_batch, prompt_len

        def prefill(p, b):              # cache comes out grown to max_len
            logits, cache = api.prefill(cfg, p, b)
            return logits, self._grow_cache(cache, L)

        self._prefill = jax.jit(prefill).lower(
            jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                         self.params),
            {"tokens": jax.ShapeDtypeStruct((B, L), jnp.int32)}).compile()
        cache_sds = api.cache_sds(cfg, B, self.max_len)
        self._decode = jax.jit(
            lambda p, c, t, q: api.decode_step(cfg, p, c, t, q, counters=True)).lower(
                jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                             self.params),
                cache_sds,
                jax.ShapeDtypeStruct((B, 1), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32)).compile()

    # --------------------------------------------------------------- serving
    def submit(self, req: GenRequest) -> None:
        with self._lock:
            self._queue.append(req)

    def step_batch(self) -> List[GenRequest]:
        """Serve one batch from the queue; returns completed requests.

        The host work is tiled by ``truffle.engine.*`` profiler spans:
        ``batch`` around the whole call (its own time is the queue pop, the
        padding and the results), ``prefill``, ``first_token``, then per
        decode step ``decode``, ``sample`` and ``token_sync``, the one place
        the loop waits on the device."""
        with jax.profiler.TraceAnnotation("truffle.engine.batch"):
            with self._lock:
                batch = self._queue[:self.max_batch]
                self._queue = self._queue[self.max_batch:]
            if not batch:
                return []
            self.last_state = None     # the previous batch's cache, freed
            B = self.max_batch
            plen = max(len(r.prompt) for r in batch)
            toks = np.zeros((B, plen), np.int32)
            for i, r in enumerate(batch):
                toks[i, plen - len(r.prompt):] = r.prompt        # left-pad
            if self.prompt_len is None:
                self.warmup(plen)
            elif plen != self.prompt_len:
                raise ValueError(f"prefill compiled for prompt length "
                                 f"{self.prompt_len}, batch pads to {plen}")

            with _span("truffle.engine.prefill", self.stats, "prefill_s"):
                logits, cache = jax.block_until_ready(
                    self._prefill(self.params, {"tokens": jnp.asarray(toks)}))

            t0 = time.monotonic()
            with jax.profiler.TraceAnnotation("truffle.engine.first_token"):
                out = np.asarray(jnp.argmax(logits[:, -1], -1)).reshape(B, 1)
                results = [out[:, 0].tolist()]
                token = jnp.asarray(out, jnp.int32)
            max_new = max(r.max_new_tokens for r in batch)
            pos = plen
            host_s, t_host = 0.0, time.monotonic()
            for _ in range(max_new - 1):
                logits, cache = self.decode(cache, token, pos)
                with jax.profiler.TraceAnnotation("truffle.engine.sample"):
                    nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
                    token = nxt[:, None]
                t_sync = time.monotonic()
                host_s += t_sync - t_host
                with jax.profiler.TraceAnnotation("truffle.engine.token_sync"):
                    results.append(np.asarray(nxt).tolist())
                t_host = time.monotonic()
                pos += 1
            self.stats.decode_s += time.monotonic() - t0
            self.stats.decode_host_s += host_s
            self.stats.decode_steps += max_new - 1
            self._read_moe_counts()
            self.last_state = DecodeState(cache, token, pos)

            gen = np.asarray(results).T                       # [B, max_new]
            for i, r in enumerate(batch):
                r.result = gen[i, :r.max_new_tokens].tolist()
                self.stats.tokens_out += len(r.result)
            return batch

    def decode(self, cache, token: jax.Array, pos: int):
        """One compiled decode step: token [B, 1] written at cache slot
        ``pos`` -> (logits [B, 1, V], grown cache)."""
        with jax.profiler.TraceAnnotation("truffle.engine.decode"):
            out = self._decode(self.params, cache, token,
                               jnp.asarray(pos, jnp.int32))
        if len(out) == 3:                  # expert-parallel MoE: its counters
            if self._moe_counts is None:   # the add compiles in the warm-up
                self._moe_counts = jnp.zeros_like(out[2])
            self._moe_counts = self._moe_counts + out[2]
        return out[:2]

    def _read_moe_counts(self) -> None:
        """Add the decode steps' MoE counters, summed on the device one
        step at a time (one shape, so nothing compiles per batch length), to
        the stats in one read."""
        if self._moe_counts is not None:
            total = np.asarray(self._moe_counts)
            self._moe_counts = None
            routed, busiest, dropped = (int(v) for v in total)
            self.stats.moe_routed += routed
            self.stats.moe_busiest += busiest
            self.stats.moe_dropped += dropped

    def _grow_cache(self, cache, plen: int):
        """Pad a cache prefilled on ``plen`` tokens out to max_len decode
        slots (``api.grow_cache``)."""
        return api.grow_cache(self.cfg, cache, self.max_len)
