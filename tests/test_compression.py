"""Gradient compression: quantization round-trip properties (single device)
and an 8-device shard_map equivalence check (subprocess: needs its own
XLA device-count flag)."""
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:       # optional dep: vendored deterministic fallback
    from _hypothesis_fallback import given, settings, strategies as st

from repro.distributed.compression import dequantize, quantize, quantization_error

SRC = str(Path(__file__).resolve().parents[1] / "src")


def test_quantize_roundtrip_bounds():
    x = jax.random.normal(jax.random.PRNGKey(0), (1024,)) * 3.0
    q, s = quantize(x)
    err = np.abs(np.asarray(x - dequantize(q, s)))
    assert err.max() <= float(s) * 0.5 + 1e-7      # half-ULP of the int8 grid


def test_quantize_zeros():
    q, s = quantize(jnp.zeros((16,)))
    np.testing.assert_array_equal(np.asarray(dequantize(q, s)), 0.0)


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 1000), scale=st.floats(1e-6, 1e4))
def test_quantize_relative_error_property(seed, scale):
    x = jax.random.normal(jax.random.PRNGKey(seed), (256,)) * scale
    q, s = quantize(x)
    err = np.abs(np.asarray(x - dequantize(q, s))).max()
    assert err <= np.abs(np.asarray(x)).max() / 127.0 * 0.5 + 1e-9


def test_error_feedback_residual():
    x = jax.random.normal(jax.random.PRNGKey(1), (512,))
    r = quantization_error(x)
    q, s = quantize(x)
    np.testing.assert_allclose(np.asarray(dequantize(q, s) + r),
                               np.asarray(x), atol=1e-6)


_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, sys.argv[1])
import functools
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.distributed.compression import compressed_grad_sync

mesh = jax.make_mesh((8,), ("data",),
                     axis_types=(jax.sharding.AxisType.Explicit,))
g = {"w": jax.random.normal(jax.random.PRNGKey(0), (8, 64, 513)),
     "b": jax.random.normal(jax.random.PRNGKey(1), (8, 33))}

@functools.partial(jax.shard_map, mesh=mesh,
                   in_specs=({"w": P("data"), "b": P("data")},),
                   out_specs={"w": P(), "b": P()}, check_vma=False)
def sync(tree):
    local = jax.tree.map(lambda x: x[0], tree)
    return compressed_grad_sync(local, "data")

out = sync(g)
want = jax.tree.map(lambda x: jnp.mean(x, 0), g)
for k in ("w", "b"):
    a, b = np.asarray(out[k]), np.asarray(want[k])
    rel = np.abs(a - b).max() / (np.abs(b).max() + 1e-9)
    assert rel < 0.02, (k, rel)   # two int8 quantization stages ~ <2% of amax
print("OK")
"""


def test_compressed_sync_8dev_subprocess():
    out = subprocess.run([sys.executable, "-c", _SUBPROC, SRC],
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout
