"""Sharding rules: divisibility fallbacks, no-axis-reuse, ZeRO-1 placement,
and the per-shape rule presets — plus a hypothesis property sweep."""
import jax
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:       # optional dep: vendored deterministic fallback
    from _hypothesis_fallback import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import (default_rules, rules_for_shape,
                                        spec_for_axes)
from repro.distributed.zero import zero1_spec


@pytest.fixture(scope="module")
def mesh():
    # 1 real device; abstract mesh construction needs none
    return jax.sharding.AbstractMesh((16, 16), ("data", "model"))


def test_spec_basic(mesh):
    rules = default_rules()
    spec = spec_for_axes(mesh, rules, (4096, 13696), ("embed", "ff"))
    assert spec == P(None, "model")


def test_spec_divisibility_fallback(mesh):
    rules = default_rules()
    # glm4: 2 kv heads cannot shard over 16-way model axis -> replicate
    spec = spec_for_axes(mesh, rules, (128, 4096, 2, 128),
                         ("cache_batch", "cache_seq", "cache_heads", None))
    assert spec in (P("data", None, None), P("data"))


def test_spec_no_axis_reuse(mesh):
    rules = default_rules()
    spec = spec_for_axes(mesh, rules, (64, 64), ("heads", "ff"))
    # both want 'model'; only the first gets it
    assert spec == P("model")


def test_decode_rules_seq_shard(mesh):
    rules = rules_for_shape("decode", global_batch=128, seq_len=32768)
    spec = spec_for_axes(mesh, rules, (40, 128, 32768, 2, 128),
                         ("layers", "cache_batch", "cache_seq", "cache_heads",
                          None))
    assert spec == P(None, "data", "model")


def test_long_context_rules(mesh):
    rules = rules_for_shape("decode", global_batch=1, seq_len=524288)
    spec = spec_for_axes(mesh, rules, (4, 1, 524288, 8, 128),
                         ("layers", "cache_batch", "cache_seq", "cache_heads",
                          None))
    assert spec == P(None, None, ("data", "model"))


def test_zero1_spec(mesh):
    # param replicated on model axis dims -> moments shard over data
    spec = zero1_spec(P(None, "model"), (4096, 13696), mesh, ("data",))
    assert spec == P("data", "model")
    # scalar: nothing to shard
    assert zero1_spec(P(), (), mesh, ("data",)) == P()
    # non-divisible: stays put
    assert zero1_spec(P(), (7,), mesh, ("data",)) == P()


@settings(max_examples=60, deadline=None)
@given(dims=st.lists(st.integers(1, 64), min_size=1, max_size=4),
       names=st.lists(st.sampled_from(["heads", "ff", "embed", "batch", None]),
                      min_size=1, max_size=4))
def test_spec_property_never_invalid(mesh, dims, names):
    """Property: produced specs never shard a non-divisible dim and never
    reuse a mesh axis across dims."""
    n = min(len(dims), len(names))
    dims, names = tuple(dims[:n]), tuple(names[:n])
    rules = default_rules()
    spec = spec_for_axes(mesh, rules, dims, names)
    used = []
    for dim, entry in zip(dims, tuple(spec) + (None,) * (n - len(spec))):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        for ax in axes:
            assert ax not in used
            used.append(ax)
        size = 1
        for ax in axes:
            size *= mesh.shape[ax]
        assert dim % size == 0
