"""Batched serving engine: request queue -> padded prefill -> greedy decode.

Truffle integration: the engine's first-batch cold start (real XLA compiles
of prefill_step + serve_step) is overlapped with SDP prefetch of request
payloads from storage — the serving twin of launch/train.py."""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
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
    time_to_first_batch: float = 0.0
    tokens_out: int = 0


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

    # ------------------------------------------------------------- lifecycle
    def warmup(self, prompt_len: int) -> None:
        """Cold start: trace+compile prefill and decode (call under Truffle's
        overlap window)."""
        t0 = time.monotonic()
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
            lambda p, c, t, q: api.decode_step(cfg, p, c, t, q)).lower(
                jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                             self.params),
                cache_sds,
                jax.ShapeDtypeStruct((B, 1), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32)).compile()
        self.stats.compile_s = time.monotonic() - t0
        self.prompt_len = prompt_len

    # --------------------------------------------------------------- serving
    def submit(self, req: GenRequest) -> None:
        with self._lock:
            self._queue.append(req)

    def step_batch(self) -> List[GenRequest]:
        """Serve one batch from the queue; returns completed requests."""
        with self._lock:
            batch = self._queue[:self.max_batch]
            self._queue = self._queue[self.max_batch:]
        if not batch:
            return []
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

        t0 = time.monotonic()
        logits, cache = jax.block_until_ready(
            self._prefill(self.params, {"tokens": jnp.asarray(toks)}))
        self.stats.prefill_s += time.monotonic() - t0

        t0 = time.monotonic()
        out = np.asarray(jnp.argmax(logits[:, -1], -1)).reshape(B, 1)
        results = [out[:, 0].tolist()]
        max_new = max(r.max_new_tokens for r in batch)
        pos = plen
        token = jnp.asarray(out, jnp.int32)
        for _ in range(max_new - 1):
            logits, cache = self.decode(cache, token, pos)
            nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
            token = nxt[:, None]
            results.append(np.asarray(nxt).tolist())
            pos += 1
        self.stats.decode_s += time.monotonic() - t0
        self.last_state = DecodeState(cache, token, pos)

        gen = np.asarray(results).T                           # [B, max_new]
        for i, r in enumerate(batch):
            r.result = gen[i, :r.max_new_tokens].tolist()
            self.stats.tokens_out += len(r.result)
        return batch

    def decode(self, cache, token: jax.Array, pos: int):
        """One compiled decode step: token [B, 1] written at cache slot
        ``pos`` -> (logits [B, 1, V], grown cache)."""
        return self._decode(self.params, cache, token,
                            jnp.asarray(pos, jnp.int32))

    def _grow_cache(self, cache, plen: int):
        """Pad prefill cache out to max_len decode slots."""
        extra = self.max_len - plen
        if extra <= 0:
            return cache

        def pad(path, a):
            name = str(getattr(path[-1], "key", ""))
            if name in ("k", "v") and a.ndim == 5:
                return jnp.pad(a, ((0, 0), (0, 0), (0, extra), (0, 0), (0, 0)))
            if name in ("ckv", "kpe") and a.ndim == 4:
                return jnp.pad(a, ((0, 0), (0, 0), (0, extra), (0, 0)))
            return a

        return jax.tree_util.tree_map_with_path(pad, cache)
