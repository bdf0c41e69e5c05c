"""A configuration file, as the benchmark runs it, and its weights.

A configuration file (``bench/configs/<name>.json``) holds the model's
published ``config.json`` keys at its top level, as run, and a ``bench``
object: the source, the program's registry entry it starts from, the name of
its plain reference (``bench/reference/<name>.py``, whose work count is
``bench/work/<name>.py``), and ``keys``, which names the published key behind
each size the program and the reference take. Architecture facts that
``config.json`` has no key for sit under ``bench.architecture``.

The reference declares the sizes it takes (``KEYS``); the file has to state
each of them, and no other. Each reaches the program's ``ModelConfig`` field
of that name; a dotted name (``mla.kv_lora_rank``) reaches into the nested
dataclass of its first part.

Weights are made here, from the seed, in one jitted call on the device and in
the dtype they are served in. The program and the reference read the same
arrays; neither makes them.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp

# Spread of the random weights: unit-scale activations through every layer,
# logits with a standard deviation near 1, and norm scales and biases that
# are not their identity, so that a path that drops them shows.
NORM_SCALE_STD = 0.1
BIAS_STD = 0.2
# The leading axis ``stack_defs`` gives a parameter repeated per layer.
STACK_AXIS = "layers"


def canonical(config: Dict[str, Any], keys: Sequence[str]) -> Dict[str, Any]:
    """The sizes the program and the reference are built from: exactly
    ``keys``, the reference's ``KEYS``, and the served dtype."""
    bench = config["bench"]
    out = {k: config[v] for k, v in bench["keys"].items()}
    for k, v in bench["architecture"].items():
        if k != "why":
            out[k] = v
    missing = [k for k in keys if k not in out]
    extra = [k for k in out if k not in keys]
    if missing or extra:
        raise ValueError(f"configuration lacks {missing} and states {extra} "
                         f"that its reference does not take")
    out["dtype"] = config["torch_dtype"]
    return out


def _replace(obj, key: str, value):
    """``obj`` with the field ``key`` (dotted: a nested dataclass's) set."""
    head, _, rest = key.partition(".")
    names = {f.name for f in dataclasses.fields(obj)} \
        if dataclasses.is_dataclass(obj) else set()
    if head not in names:
        raise ValueError(f"the program's {type(obj).__name__} has no field "
                         f"{head!r} (for {key!r})")
    if rest:
        value = _replace(getattr(obj, head), rest, value)
    return dataclasses.replace(obj, **{head: value})


def program_config(config: Dict[str, Any], c: Dict[str, Any]):
    """The program's ModelConfig: its registry entry, served in the compute
    dtype (``serving_config``), with every size of ``c`` (``canonical``)."""
    from repro.launch.serve import serving_config

    cfg = serving_config(config["bench"]["arch"], smoke=False)
    if cfg.dtype != c["dtype"]:
        raise ValueError(f"program computes in {cfg.dtype}, the configuration "
                         f"states {c['dtype']}")
    for k, v in c.items():
        if k != "dtype":
            cfg = _replace(cfg, k, v)
    return cfg


def weight_shapes(cfg) -> Dict[str, Any]:
    """The program's parameter tree as ShapeDtypeStructs."""
    from repro.models import api

    return api.abstract(cfg)


def _leaf_init(path, d) -> str:
    """How the benchmark draws one leaf, from the program's ``ParamDef``:
    scales (``ones``) and biases (``zeros``) off their identity, a table
    looked up by token id (first axis ``vocab``) by its width, and every
    other matrix by its fan-in."""
    if d.init == "ones":
        return "norm"
    if d.init == "zeros":
        return "bias"
    if d.init == "normal":
        if d.axes[0] == "vocab":
            return "embedding"
        if len([a for a in d.axes if a != STACK_AXIS]) >= 2:
            return "dense"
    raise ValueError(f"no initialiser for parameter "
                     f"{jax.tree_util.keystr(path)} ({d.init}, axes {d.axes})")


def make_weights(cfg, seed: int):
    """Every leaf of the program's parameter tree drawn from ``seed`` in one
    jitted call."""
    return weights_program(cfg)(seed_key(seed))


def weights_program(cfg):
    """The jitted function of a PRNG key that makes the weights, leaf ``i``
    of the tree (in its flattening order) from ``fold_in(key, i)``."""
    from repro.models import api
    from repro.models.params import ParamDef

    paths, treedef = jax.tree_util.tree_flatten_with_path(
        api.model_defs(cfg), is_leaf=lambda x: isinstance(x, ParamDef))
    specs = [(_leaf_init(p, d), d.shape, jnp.dtype(d.dtype or cfg.param_dtype))
             for p, d in paths]

    def bench_weights(key):
        leaves = []
        for i, (kind, shape, dtype) in enumerate(specs):
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
