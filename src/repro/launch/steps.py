"""The train step, with full sharding trees.

``build_train_step`` returns ``(fn, abstract_args)`` where abstract_args is a
tree of ShapeDtypeStructs *carrying NamedShardings* — ready both for AOT
``jax.jit(fn).lower(*abstract_args)`` and for real execution with concrete
arrays laid out the same way.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.distributed.sharding import (ShardCtx, dp_axes,
                                        rules_for_shape, spec_for_axes,
                                        specs_for, shardings_for)
from repro.distributed.zero import zero1_specs
from repro.models import api
from repro.models.params import abstract_params
from repro.optim.adamw import OptConfig, apply_updates, init_state

PyTree = Any


def _sds(shape, dtype, mesh, spec: P):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, spec))


def _with_shardings(sds_tree: PyTree, axes_tree: PyTree, mesh: Mesh, rules) -> PyTree:
    def f(s: jax.ShapeDtypeStruct, ax):
        spec = spec_for_axes(mesh, rules, s.shape, ax)
        return _sds(s.shape, s.dtype, mesh, spec)
    return jax.tree.map(f, sds_tree, axes_tree,
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def _batch_axes(batch_sds: Dict[str, Any]) -> Dict[str, Any]:
    ax = {}
    for k, v in batch_sds.items():
        if k in ("tokens", "labels", "token"):
            ax[k] = ("batch", None)
        elif k == "mrope_positions":
            ax[k] = ("batch", None, None)
        else:  # frames / vision_embeds
            ax[k] = ("batch", None, None)
    return ax


# ---------------------------------------------------------------------------
# Params / state
# ---------------------------------------------------------------------------

def abstract_param_tree(cfg: ModelConfig, mesh: Mesh, rules) -> PyTree:
    defs = api.model_defs(cfg)
    sds = abstract_params(defs, cfg.param_dtype)
    shardings = shardings_for(defs, mesh, rules)
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        sds, shardings,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def abstract_train_state(cfg: ModelConfig, mesh: Mesh, rules,
                         zero1: bool = True) -> PyTree:
    defs = api.model_defs(cfg)
    p = abstract_param_tree(cfg, mesh, rules)
    if zero1:
        zspecs = zero1_specs(defs, mesh, rules)
    else:
        zspecs = specs_for(defs, mesh, rules)
    moment = jax.tree.map(
        lambda s, sp: _sds(s.shape, jnp.float32, mesh, sp), p, zspecs,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    return {"params": p,
            "opt": {"m": moment, "v": jax.tree.map(lambda x: x, moment),
                    "step": _sds((), jnp.int32, mesh, P())}}


def concrete_train_state(cfg: ModelConfig, mesh: Optional[Mesh], rules, key) -> PyTree:
    params = api.init(cfg, key)
    opt = init_state(params)
    state = {"params": params, "opt": opt}
    if mesh is not None:
        abstract = abstract_train_state(cfg, mesh, rules)
        state = jax.tree.map(lambda x, s: jax.device_put(x, s.sharding),
                             state, abstract)
    return state


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------

def build_train_step(cfg: ModelConfig, mesh: Mesh, shape: ShapeConfig, *,
                     opt_cfg: Optional[OptConfig] = None, microbatch: int = 1,
                     multi_pod: bool = False, zero1: bool = True,
                     rules: Optional[Dict[str, Any]] = None):
    rules = rules or rules_for_shape("train", multi_pod=multi_pod)
    ctx = ShardCtx(mesh, rules)
    opt_cfg = opt_cfg or OptConfig()

    def train_step(state, batch):
        params = state["params"]

        def lf(p, b):
            return api.loss_fn(cfg, p, b, ctx)

        if microbatch > 1:
            def micro(carry, mb):
                g_acc, l_acc = carry
                (loss, _), g = jax.value_and_grad(lf, has_aux=True)(params, mb)
                g_acc = jax.tree.map(lambda a, b_: a + b_.astype(jnp.float32), g_acc, g)
                return (g_acc, l_acc + loss), None

            dpn = 1
            for a in dp_axes(rules):
                if a in mesh.shape:
                    dpn *= mesh.shape[a]

            def split(x):
                # Shard-aligned microbatching: split WITHIN each DP shard
                # ([B] -> [dp, mb, B/(dp*mb)] -> [mb, B/mb]) so every
                # microbatch keeps the original batch sharding. The naive
                # contiguous split makes GSPMD resort to involuntary full
                # rematerialization (§Perf log, jamba iteration 2).
                nb = x.shape[0] // microbatch
                if x.shape[0] % (dpn * microbatch) == 0:
                    x = x.reshape(dpn, microbatch, nb // dpn, *x.shape[1:])
                    x = jnp.moveaxis(x, 1, 0)
                    return x.reshape(microbatch, nb, *x.shape[3:])
                return x.reshape(microbatch, nb, *x.shape[1:])

            mbs = jax.tree.map(split, batch)
            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss), _ = jax.lax.scan(micro, (g0, jnp.zeros((), jnp.float32)), mbs)
            grads = jax.tree.map(lambda g: g / microbatch, grads)
            loss = loss / microbatch
            metrics = {"loss": loss}
        else:
            (loss, metrics), grads = jax.value_and_grad(lf, has_aux=True)(params, batch)

        new_p, new_opt, om = apply_updates(opt_cfg, params, grads, state["opt"])
        metrics = dict(metrics)
        metrics.update(om)
        return {"params": new_p, "opt": new_opt}, metrics

    state_sds = abstract_train_state(cfg, mesh, rules, zero1=zero1)
    batch_raw = api.input_specs(cfg, shape)["batch"]
    batch_sds = _with_shardings(batch_raw, _batch_axes(batch_raw), mesh, rules)
    return train_step, (state_sds, batch_sds)
