"""Checkpointing: roundtrip equality, atomicity/rotation, async saves,
restore-latest, byte-stream serialize (the CSP payload path) and its raw
typed wire format."""
import contextlib
import io
import struct
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import checkpoint
from repro.checkpoint.checkpoint import (CheckpointManager, deserialize,
                                         serialize)

_HEAD = struct.Struct("<8sQ")    # magic, header length


def _state(seed=0):
    k = jax.random.PRNGKey(seed)
    return {
        "params": {"w": jax.random.normal(k, (8, 16)),
                   "blocks": {"scale": jnp.ones((4,), jnp.bfloat16)}},
        "opt": {"m": jnp.zeros((8, 16)), "step": jnp.asarray(7, jnp.int32)},
    }


def _assert_tree_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))


def test_roundtrip(tmp_path):
    mgr = CheckpointManager(tmp_path)
    s = _state()
    mgr.save(3, s)
    restored, step = mgr.restore(_state(seed=9))
    assert step == 3
    _assert_tree_equal(s, restored)


def test_latest_and_rotation(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2)
    for step in (1, 2, 3, 4):
        mgr.save(step, _state(step))
    assert mgr.latest_step() == 4
    assert mgr.all_steps() == [3, 4]          # rotated
    restored, step = mgr.restore(_state())
    _assert_tree_equal(_state(4), restored)


def test_async_save(tmp_path):
    mgr = CheckpointManager(tmp_path)
    t = mgr.save_async(5, _state(5))
    mgr.wait()
    assert mgr.latest_step() == 5
    restored, _ = mgr.restore(_state())
    _assert_tree_equal(_state(5), restored)


def test_sharded_save(tmp_path):
    mgr = CheckpointManager(tmp_path, shard_bytes=128)  # force many shards
    s = _state()
    mgr.save(1, s)
    d = mgr.dir / "step-00000001"
    assert len(list(d.glob("shard-*.npz"))) > 1
    restored, _ = mgr.restore(_state(2))
    _assert_tree_equal(s, restored)


def test_serialize_bytes_roundtrip():
    s = _state()
    data = serialize(s)
    assert memoryview(data).nbytes == len(data) > 100      # bytes-like
    for payload in (data, bytes(data)):
        _assert_tree_equal(s, deserialize(payload, _state(1)))


def test_restore_missing_raises(tmp_path):
    mgr = CheckpointManager(tmp_path)
    with pytest.raises(FileNotFoundError):
        mgr.restore(_state())


_RNG = np.random.default_rng(0)
LEAVES = {
    "bf16": jnp.asarray(_RNG.normal(size=(3, 5)), jnp.bfloat16),
    "f16": jnp.asarray(_RNG.normal(size=(7,)), jnp.float16),
    "f32": jnp.asarray(_RNG.normal(size=(2, 3, 4)), jnp.float32),
    "int8": jnp.asarray(_RNG.integers(-128, 128, size=(9,)), jnp.int8),
    "int32": jnp.asarray(_RNG.integers(-2**31, 2**31, size=(4, 2)), jnp.int32),
    "bool": jnp.asarray(_RNG.integers(0, 2, size=(11,)), jnp.bool_),
    "0-d": jnp.asarray(1.25, jnp.bfloat16),
    "empty": jnp.zeros((0, 4), jnp.bfloat16),
    "transposed": np.arange(24, dtype=np.float32).reshape(4, 6).T,
    "scalar": 7,
    "int4": jnp.arange(-3, 4, dtype=jnp.int4),
}
HOST_LEAVES = ["transposed", "scalar", "int4"]    # the device cannot pack these
DEVICE_PACK = "truffle.csp.device_pack"


def _bits(x):
    return np.ascontiguousarray(np.asarray(x)).view(np.uint8).tobytes()


def _with_neighbours(leaf):
    """``leaf`` with an odd-sized neighbour on each side, so every offset is
    exercised."""
    return {"a": jnp.arange(3, dtype=jnp.int8), "leaf": leaf,
            "z": jnp.ones((5,), jnp.bfloat16)}


@pytest.fixture
def spans(monkeypatch):
    """Names of the profiler annotations opened, in order."""
    names = []
    real = jax.profiler.TraceAnnotation

    @contextlib.contextmanager
    def annotation(name, **kw):
        names.append(name)
        with real(name, **kw):
            yield
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", annotation)
    return names


@pytest.mark.parametrize("name", list(LEAVES))
def test_wire_roundtrip_is_bit_exact(name):
    tree = _with_neighbours(LEAVES[name])
    back = deserialize(serialize(tree), like=tree)
    for key, want in tree.items():
        got = back[key]
        assert np.shape(got) == np.shape(want)
        assert got.dtype == np.asarray(want).dtype
        assert _bits(got) == _bits(want), key


def test_payload_is_header_plus_aligned_leaves():
    tree = {k: v for k, v in LEAVES.items() if k not in ("scalar", "int4")}
    payload = serialize(tree)
    magic, n = _HEAD.unpack_from(payload)
    aligned = lambda b: -(-b // 64) * 64
    data = sum(aligned(np.asarray(v).nbytes) for v in tree.values())
    assert magic == b"TRFCSP01"
    assert len(payload) == aligned(_HEAD.size + n) + data


def test_leaves_come_back_as_read_only_views():
    tree = {"k": LEAVES["bf16"], "n": LEAVES["int32"]}
    back = deserialize(serialize(tree), like=tree)
    for v in back.values():
        assert not v.flags.writeable and not v.flags.owndata


def test_missing_leaf_raises_key_error():
    payload = serialize({"a": LEAVES["f32"]})
    with pytest.raises(KeyError, match="b"):
        deserialize(payload, like={"a": LEAVES["f32"], "b": LEAVES["f32"]})


def _npz_payload():
    buf = io.BytesIO()
    np.savez(buf, a=np.ones(3, np.float32))
    return buf.getvalue()


@pytest.mark.parametrize("payload", [_npz_payload(), b"", b"TRFCSP",
                                     b"\x00" * 64], ids=["npz", "empty",
                                                         "short", "zeros"])
def test_foreign_payload_raises_value_error(payload):
    with pytest.raises(ValueError, match="not a CSP payload"):
        deserialize(payload, like={"a": np.ones(3, np.float32)})


def test_dtype_mismatch_casts_under_one_narrow(spans):
    stored = {"k": LEAVES["f32"], "n": LEAVES["int32"]}
    payload = serialize(stored)
    del spans[:]
    like = {"k": jnp.zeros((2, 3, 4), jnp.bfloat16), "n": LEAVES["int32"]}
    back = deserialize(payload, like=like)
    assert spans == ["truffle.csp.deserialize", "truffle.csp.unpack",
                     "truffle.csp.narrow"]
    assert back["k"].dtype == jnp.bfloat16
    assert _bits(back["k"]) == _bits(np.asarray(stored["k"]).astype(jnp.bfloat16))
    assert _bits(back["n"]) == _bits(stored["n"])


@pytest.mark.parametrize("name", [k for k in LEAVES if k not in HOST_LEAVES])
def test_device_pack_equals_host_pack(name, spans):
    tree = _with_neighbours(LEAVES[name])
    payload = serialize(tree)
    assert spans.count(DEVICE_PACK) == 1
    del spans[:]
    host = serialize(jax.tree.map(np.asarray, tree))
    assert DEVICE_PACK not in spans
    assert bytes(payload) == bytes(host)


@pytest.mark.parametrize("name", HOST_LEAVES)
def test_a_host_leaf_takes_the_host_path(name, spans):
    tree = _with_neighbours(LEAVES[name])
    payload = serialize(tree)
    assert DEVICE_PACK not in spans
    assert spans.count("truffle.csp.d2h") == len(tree)
    back = deserialize(payload, like=tree)
    assert _bits(back["leaf"]) == _bits(tree["leaf"])


def test_pack_program_compiles_once_per_shape():
    first = {"p": jnp.ones((13, 7), jnp.float32), "q": jnp.arange(29, dtype=jnp.int8)}
    second = {"p": first["p"] * 2, "q": first["q"] + 1}
    n = checkpoint._pack._cache_size()
    payloads = [bytes(serialize(t)) for t in (first, second)]
    assert checkpoint._pack._cache_size() == n + 1
    assert payloads[0] != payloads[1]
    for tree, payload in zip((first, second), payloads):
        back = deserialize(payload, like=tree)
        assert all(_bits(back[k]) == _bits(v) for k, v in tree.items())
