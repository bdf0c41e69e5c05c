"""Parameter definition trees.

A module's parameters are declared once as a nested dict of ``ParamDef``
leaves (shape + logical axes + init). From that single source of truth we
derive:
  * initialized arrays            (``init_params``)
  * PartitionSpecs for the mesh   (``distributed.sharding.specs_for``)
  * stacked per-layer variants    (``stack_defs``) for ``lax.scan`` stacks
"""
from __future__ import annotations

import functools
import zlib
from dataclasses import dataclass, replace
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

PyTree = Any


@dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]   # logical axis names (len == len(shape))
    init: str = "normal"              # normal | zeros | ones
    scale: float = 1.0                # stddev for "normal"
    dtype: Optional[str] = None       # override canonical param dtype

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def dense(d_in: int, d_out: int, axes: Tuple[Optional[str], ...],
          scale: Optional[float] = None) -> ParamDef:
    """Dense matrix with fan-in init."""
    return ParamDef((d_in, d_out), axes, "normal",
                    scale if scale is not None else d_in ** -0.5)


def stack_defs(defs: PyTree, n: int, axis: Optional[str] = None) -> PyTree:
    """Prepend a leading layer-stack dim of size ``n`` to every leaf."""
    def f(d: ParamDef) -> ParamDef:
        return replace(d, shape=(n,) + d.shape, axes=(axis,) + d.axes)
    return jax.tree.map(f, defs, is_leaf=lambda x: isinstance(x, ParamDef))


def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _normal(key: jax.Array, shape: Tuple[int, ...], scale: float, dtype: str):
    """One leaf's f32 draw fused with its cast, so a bf16 leaf never holds a
    full-size f32 copy on the device."""
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def init_params(defs: PyTree, key: jax.Array, param_dtype: str = "float32") -> PyTree:
    """Initialize arrays from a def tree (path-stable RNG per leaf)."""
    def init_leaf(path, d: ParamDef):
        dtype = d.dtype or param_dtype
        if d.init == "zeros":
            return jnp.zeros(d.shape, dtype)
        if d.init == "ones":
            return (jnp.ones(d.shape, jnp.float32) * d.scale).astype(dtype)
        leaf_key = jax.random.fold_in(key, zlib.crc32(_path_str(path).encode()))
        return _normal(leaf_key, d.shape, d.scale, dtype)
    return jax.tree_util.tree_map_with_path(
        init_leaf, defs, is_leaf=lambda x: isinstance(x, ParamDef))


def abstract_params(defs: PyTree, param_dtype: str = "float32") -> PyTree:
    """ShapeDtypeStructs for the def tree (no allocation)."""
    def f(d: ParamDef) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(d.shape, jnp.dtype(d.dtype or param_dtype))
    return jax.tree.map(f, defs, is_leaf=lambda x: isinstance(x, ParamDef))


def count_params(defs: PyTree) -> int:
    leaves = jax.tree.leaves(defs, is_leaf=lambda x: isinstance(x, ParamDef))
    total = 0
    for d in leaves:
        n = 1
        for s in d.shape:
            n *= s
        total += n
    return total
