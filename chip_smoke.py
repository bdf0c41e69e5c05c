"""Bring-up check on one TPU: serve qwen3-4b at full width through
``repro.launch.serve`` and hand its KV cache off through the data plane.

  python chip_smoke.py

Everything runs in this one process, since a chip belongs to one process at
a time. It refuses to start unless JAX's first device is a TPU; it never
falls back to the CPU. Phases:

  serve    8 requests, batch 4, prompt length 128, 16 new tokens, Truffle
           overlap on, random bf16 weights from a fixed seed. Every greedy
           token must be the argmax of one full-sequence prefill over
           prompt + generated tokens, except where that prefill's top logits
           lie within NEAR_TIE_ULPS bf16 ulps of each other.
  handoff  the grown KV cache (Truffle's CSP payload) is serialized, shipped
           through a simulated link, restored to the device, and one decode
           step from it must be bitwise equal to one from the cache that
           never left.

The compile cache is ``$JAX_COMPILATION_CACHE_DIR`` if set, else
``<checkout>/.jax_cache``. The last line of stdout is one JSON object naming
the device; a failed phase raises, exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.checkpoint.checkpoint import deserialize, serialize  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import use_compilation_cache  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.runtime.clock import Clock  # noqa: E402
from repro.runtime.netsim import GBPS, Channel  # noqa: E402

ARCH = "qwen3-4b"
REQUESTS, BATCH, PROMPT_LEN, MAX_NEW = 8, 4, 128, 16
# A greedy token may differ from the full prefill's argmax only where the
# decoded token's prefill logit is within this many bf16 ulps (of the top
# logit's magnitude) of the top: both paths round their logits to bf16, and
# prefill and decode accumulate in different orders.
NEAR_TIE_ULPS = 4
# Handoff link: the 450 Mbit/s edge uplink of benchmarks/serve_handoff.py,
# simulated seconds scaled 10x down to wall time.
LINK_GBPS, LINK_LATENCY_S, CLOCK_SCALE = 0.45, 0.0005, 0.1


class CheckFailed(RuntimeError):
    pass


def _log(msg: str) -> None:
    print(msg, flush=True)


def _peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats()
    return stats.get("peak_bytes_in_use") if stats else None


def greedy_vs_prefill(engine, done) -> dict:
    """Check each request's greedy tokens against one full-sequence prefill
    over prompt + generated tokens; raise on a mismatch that is no near tie."""
    cfg, L = engine.cfg, engine.prompt_len
    n = len(done[0].result)
    seqs = np.array([r.prompt + r.result[:-1] for r in done], np.int32)
    got = np.array([r.result for r in done])                       # [R, n]

    @jax.jit
    def logits_predicting_generated(params, tokens):
        h, _, _ = lm.forward(cfg, params, tokens, mode="prefill")
        # position i predicts token i + 1: positions L-1 .. L+n-2 -> [R, n, V]
        return jnp.concatenate([lm.logits_at_last(cfg, params, h[:, :i + 1])
                                for i in range(L - 1, L + n - 1)], axis=1)

    logits = np.asarray(logits_predicting_generated(engine.params,
                                                    jnp.asarray(seqs)))
    if not np.isfinite(logits).all():
        raise CheckFailed("full-sequence prefill produced non-finite logits")
    top1 = logits.max(-1)
    top2 = np.partition(logits, -2, axis=-1)[..., -2]
    below_top = top1 - np.take_along_axis(logits, got[..., None], -1)[..., 0]
    ulp = float(jnp.finfo(jnp.bfloat16).eps) * 2.0 ** np.floor(
        np.log2(np.maximum(np.abs(top1), 1e-30)))
    tol = NEAR_TIE_ULPS * ulp
    mismatch = got != logits.argmax(-1)
    near_tie = (top1 - top2) <= tol
    bad = mismatch & (below_top > tol)
    if bad.any():
        r, i = np.argwhere(bad)[0]
        raise CheckFailed(
            f"greedy token differs from the full prefill's argmax at "
            f"{int(bad.sum())} positions beyond the near-tie tolerance; first: "
            f"request {r} token {i}: decoded {got[r, i]} sits "
            f"{below_top[r, i]:.6g} below the top logit {top1[r, i]:.6g} "
            f"(tolerance {tol[r, i]:.6g})")
    return {"positions": int(got.size), "mismatches": int(mismatch.sum()),
            "near_tie_positions": int(near_tie.sum()),
            "max_tolerance": float(tol.max())}


def phase_serve(*, smoke: bool = False) -> serve.ServeRun:
    """Serve through ``repro.launch.serve.main``, then check its tokens."""
    argv = ["--arch", ARCH, "--requests", str(REQUESTS), "--batch", str(BATCH),
            "--prompt-len", str(PROMPT_LEN), "--max-new", str(MAX_NEW)]
    run = serve.main(argv + ([] if smoke else ["--no-smoke"]))
    eng, cfg, st = run.engine, run.engine.cfg, run.engine.stats
    _log(f"serve: {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
         f"heads={cfg.num_heads}/{cfg.num_kv_heads} vocab={cfg.vocab_size} "
         f"param_dtype={cfg.param_dtype}")
    _log(f"serve: compile_s={st.compile_s} prefill_s={st.prefill_s} "
         f"decode_s={st.decode_s} total_s={run.total_s}")
    _log(f"serve: requests={len(run.done)} tokens_out={st.tokens_out} "
         f"peak_bytes_in_use={_peak_bytes()}")
    if len(run.done) != REQUESTS or st.tokens_out != REQUESTS * MAX_NEW:
        raise CheckFailed(f"served {len(run.done)} requests and "
                          f"{st.tokens_out} tokens, want {REQUESTS} and "
                          f"{REQUESTS * MAX_NEW}")
    t0 = time.monotonic()
    chk = greedy_vs_prefill(eng, run.done)
    _log(f"serve: greedy-vs-prefill passed: {chk['positions']} positions, "
         f"{chk['mismatches']} mismatches (each a near tie), "
         f"{chk['near_tie_positions']} near-tie positions; tolerance "
         f"{NEAR_TIE_ULPS} bf16 ulps of the top logit (at most "
         f"{chk['max_tolerance']:.6g}); check_s={time.monotonic() - t0}")
    return run


def phase_handoff(run: serve.ServeRun) -> dict:
    """Ship the last batch's grown KV cache and decode one step from it."""
    eng, state = run.engine, run.engine.last_state
    raw_bytes = sum(x.nbytes for x in jax.tree.leaves(state.cache))
    payload = serialize(state.cache)
    link = Channel("prefill->decode", LINK_GBPS * GBPS, LINK_LATENCY_S,
                   Clock(CLOCK_SCALE))
    sim_s = link.transfer(payload)
    restored = jax.device_put(deserialize(payload, like=state.cache))
    shipped, _ = eng.decode(restored, state.token, state.pos)
    kept, _ = eng.decode(state.cache, state.token, state.pos)
    shipped, kept = np.asarray(shipped), np.asarray(kept)
    if not np.isfinite(kept).all():
        raise CheckFailed("decode from the kept cache is not finite")
    if shipped.dtype != kept.dtype or shipped.tobytes() != kept.tobytes():
        raise CheckFailed("decode from the shipped cache differs bitwise from "
                          "decode from the kept cache")
    out = {"payload_bytes": len(payload), "raw_bytes": raw_bytes,
           "sim_s": sim_s}
    _log(f"handoff: payload_bytes={len(payload)} raw_cache_bytes={raw_bytes} "
         f"ratio={len(payload) / raw_bytes} link_sim_s={sim_s}; decode from "
         f"the shipped cache is bitwise equal (logits {kept.shape})")
    return out


def _cache_entries(path: Path) -> int:
    return sum(1 for _ in path.iterdir()) if path.is_dir() else 0


def main(argv=None) -> int:
    argparse.ArgumentParser(description=__doc__.split("\n\n")[0]).parse_args(argv)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's first device is "
              f"{dev.platform} ({dev.device_kind})", file=sys.stderr)
        return 1
    _log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")
    cache_dir = use_compilation_cache()
    before = _cache_entries(cache_dir)
    run = phase_serve()
    phase_handoff(run)
    _log(f"compile cache: {cache_dir} entries {before} -> "
         f"{_cache_entries(cache_dir)}")
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(devices)}}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
