"""Compile a cell's programs at full size for a described TPU v5e, with no
chip attached, and print what each needs of the device's memory.

  JAX_PLATFORMS=cpu python3 bench/rehearse.py <cell> [<cell> ...]

Compiles the weight maker, the engine's prefill and decode programs as
``ServeEngine.warmup`` builds them, and one layer of the reference at the
check's sizes. A program that does not fit, or that the TPU compiler
refuses, fails here at no chip time.
"""
from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _mem(compiled) -> dict:
    m = compiled.memory_analysis()
    return {k: getattr(m, k) for k in ("argument_size_in_bytes",
                                       "output_size_in_bytes",
                                       "temp_size_in_bytes",
                                       "alias_size_in_bytes")}


def rehearse(cell_name: str) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import model
    from bench.harness import Bench
    from repro.models import api
    from repro.serving.engine import ServeEngine

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)

    bench = Bench()
    cell = bench.cell(cell_name)
    config, mix = bench.config(cell["config"]), bench.mix(cell["traffic"])
    refmod = bench.reference(config)
    c = model.canonical(config, refmod.KEYS)
    cfg = model.program_config(config, c)
    B, L, n = mix["clients"], mix["prompt_len"], mix["new_tokens"]
    shapes = model.weight_shapes(cfg)
    p_sds = jax.tree.map(sds, shapes)
    out = {}

    def timed(name, lowered):
        t = time.monotonic()
        compiled = lowered.compile()
        out[name] = dict(_mem(compiled), compile_s=time.monotonic() - t)
        print(cell_name, name, out[name], file=sys.stderr, flush=True)

    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
    timed("weights", model.weights_program(cfg).lower(key))
    engine = ServeEngine(cfg, None, max_batch=B, max_len=L + n)

    def prefill(p, b):
        logits, cache = api.prefill(cfg, p, b)
        return logits, engine._grow_cache(cache, L)

    timed("prefill", jax.jit(prefill).lower(
        p_sds, {"tokens": jax.ShapeDtypeStruct((B, L), jnp.int32, sharding=one)}))
    timed("decode", jax.jit(lambda p, cc, t, q: api.decode_step(cfg, p, cc, t, q)).lower(
        p_sds, jax.tree.map(sds, api.cache_sds(cfg, B, L + n)),
        jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one)))
    ref = refmod.Reference(c)
    T = L + n - 1
    rows = max(1, min(mix["check_requests"],
                      int(refmod.SCORE_BYTES // (c["num_heads"] * T * T * 4))))
    half = ref.rot // 2
    x = jax.ShapeDtypeStruct((rows, T, c["d_model"]), jnp.float32, sharding=one)
    tab = jax.ShapeDtypeStruct((T, half), jnp.float32, sharding=one)
    timed("reference_layer", ref._layer.lower(
        x, p_sds["blocks"], jax.ShapeDtypeStruct((), jnp.int32, sharding=one), tab, tab))
    return out


if __name__ == "__main__":
    print(json.dumps({c: rehearse(c) for c in sys.argv[1:]}, indent=1))
