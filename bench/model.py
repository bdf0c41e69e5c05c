"""A configuration file, as the benchmark runs it, and its weights.

A configuration file (``bench/configs/<name>.json``) holds the model's
published ``config.json`` keys at its top level, as run, and a ``bench``
object: the source, the program's registry entry it starts from, the name of
its plain reference (``bench/reference/<name>.py``), and ``keys``, which
names the published key behind each size the program and the reference take.
Architecture facts that ``config.json`` has no key for sit under
``bench.architecture``.

Weights are made here, from the seed, in one jitted call on the device and in
the dtype they are served in. The program and the reference read the same
arrays; neither makes them.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

# Sizes and switches both the program and the reference take, by the name the
# reference uses; each comes from bench.keys (a published key) or
# bench.architecture.
CANONICAL = ("num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim",
             "d_ff", "vocab_size", "norm_eps", "rope_theta", "tie_embeddings",
             "qkv_bias", "qk_norm", "partial_rotary")

# Spread of the random weights: unit-scale activations through every layer,
# logits with a standard deviation near 1, and norm scales and biases that
# are not their identity, so that a path that drops them shows.
NORM_SCALE_STD = 0.1
BIAS_STD = 0.2


def canonical(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the program and the reference are built from."""
    bench = config["bench"]
    out = {k: config[v] for k, v in bench["keys"].items()}
    for k, v in bench["architecture"].items():
        if k != "why":
            out[k] = v
    missing = [k for k in CANONICAL if k not in out]
    if missing:
        raise ValueError(f"configuration lacks {missing}")
    out["dtype"] = config["torch_dtype"]
    return out


def program_config(config: Dict[str, Any]):
    """The program's ModelConfig: its registry entry, served in the compute
    dtype (``serving_config``), with every size the file states."""
    from repro.launch.serve import serving_config

    c = canonical(config)
    cfg = serving_config(config["bench"]["arch"], smoke=False)
    if cfg.dtype != c["dtype"]:
        raise ValueError(f"program computes in {cfg.dtype}, the configuration "
                         f"states {c['dtype']}")
    return cfg.replace(**{k: c[k] for k in CANONICAL})


def weight_shapes(cfg) -> Dict[str, Any]:
    """The program's parameter tree as ShapeDtypeStructs."""
    from repro.models import api

    return api.abstract(cfg)


def _leaf_init(path) -> str:
    name = str(getattr(path[-1], "key", path[-1]))
    parent = str(getattr(path[-2], "key", "")) if len(path) > 1 else ""
    if name in ("q_norm", "k_norm") or (name == "scale" and parent.endswith("norm")):
        return "norm"
    if name in ("bq", "bk", "bv"):
        return "bias"
    if name == "embedding":
        return "embedding"
    if name.startswith("w") or name == "unembed":
        return "dense"
    raise ValueError(f"no initialiser for parameter {'/'.join(map(str, path))}")


def make_weights(shapes, seed: int):
    """Every leaf of ``shapes`` drawn from ``seed`` in one jitted call."""
    return weights_program(shapes)(seed_key(seed))


def weights_program(shapes):
    """The jitted function of a PRNG key that makes the weights."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    specs = [(p, _leaf_init(p), s.shape, s.dtype) for p, s in paths]

    def bench_weights(key):
        leaves = []
        for i, (_, kind, shape, dtype) in enumerate(specs):
            z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
            if kind == "norm":
                z = 1.0 + NORM_SCALE_STD * z
            elif kind == "bias":
                z = BIAS_STD * z
            elif kind == "embedding":
                z = z * shape[-1] ** -0.5
            else:                                    # fan-in scaled matrix
                z = z * shape[-2] ** -0.5
            leaves.append(z.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(bench_weights)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, also past 32 bits."""
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)
