"""The comparison that decides ``correct``.

Once the window has closed, a sample of the requests it finished is drawn
from the seed. For each, the plain reference runs once over the prompt and
the served tokens, and at every served position reads how far the served
token's logit lies below the reference's best logit there. The number
compared is the widest such gap over the sample (``max_gap``), against the
cell's limit in ``bench/limits/<cell>.json``. Greedy serving puts the argmax
first, so a sound served path reads gaps of rounding size only, at near ties.

The control (calibration only, never in a benchmark run) reads, at the same
positions, the gap of the token that the reference computed in float8 would
put first.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Sequence

import numpy as np


@dataclass
class Served:
    """One finished request: its prompt and the tokens served for it."""
    prompt: List[int]
    tokens: List[int]


def sample(finished: Sequence[Served], n: int, seed: int) -> List[Served]:
    """``n`` requests drawn from the seed, the longest among them."""
    if not finished:
        return []
    order = np.random.default_rng([seed, 0xC4EC]).permutation(len(finished))
    longest = max(range(len(finished)), key=lambda i: len(finished[i].tokens))
    picked = [longest] + [int(i) for i in order if i != longest][:max(n - 1, 0)]
    return [finished[i] for i in picked]


def _rows(reqs: Sequence[Served]):
    """Teacher-forced rows: prompt + served[:-1]; position i predicts served[i]."""
    L, n = len(reqs[0].prompt), len(reqs[0].tokens)
    if any(len(r.prompt) != L or len(r.tokens) != n for r in reqs):
        raise ValueError("sampled requests differ in length")
    toks = np.array([r.prompt + r.tokens[:-1] for r in reqs], np.int32)
    served = np.array([r.tokens for r in reqs], np.int64)
    return toks, list(range(L - 1, L + n - 1)), served


def gaps_of(logits: np.ndarray, chosen: np.ndarray) -> np.ndarray:
    """Reference best logit minus the chosen token's, per position; a token
    outside the vocabulary reads +inf."""
    V = logits.shape[-1]
    ok = (chosen >= 0) & (chosen < V)
    picked = np.take_along_axis(logits, np.where(ok, chosen, 0)[..., None], -1)[..., 0]
    gap = logits.max(-1) - picked
    return np.where(ok & np.isfinite(gap), gap, np.inf)


def served_gaps(ref, weights, reqs: Sequence[Served]) -> np.ndarray:
    toks, positions, served = _rows(reqs)
    return gaps_of(ref.logits(weights, toks, positions), served)


def greedy(ref, weights, prompts: Sequence[List[int]], n: int) -> np.ndarray:
    """``n`` greedy tokens of ``ref`` after each prompt (all of one length),
    [R, n]: a reference decoding in the served path's place. One buffer of
    the final length serves every step, so the reference compiles once."""
    buf = np.array(prompts, np.int32)
    R, L = buf.shape
    buf = np.concatenate([buf, np.zeros((R, max(n - 1, 0)), np.int32)], 1)
    out = np.zeros((R, n), np.int64)
    for k in range(n):
        out[:, k] = ref.logits(weights, buf, [L - 1 + k]).argmax(-1)[:, 0]
        if k < n - 1:
            buf[:, L + k] = out[:, k]
    return out


def control_gaps(ref, ref_low, weights, reqs: Sequence[Served]) -> np.ndarray:
    """Gaps, in the reference, of the tokens the lower precision puts first."""
    toks, positions, _ = _rows(reqs)
    low = ref_low.logits(weights, toks, positions)
    return gaps_of(ref.logits(weights, toks, positions), low.argmax(-1))
