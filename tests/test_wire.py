"""Wire codec tests: bit-exact round trips, integrity checking, and the
measured-size contract (serialized size == content + bounded framing)."""

import struct
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.comm import (
    WireError, decode_update, encode_update, update_nbytes,
)
from repro.comm.wire import (
    _HEADER, WIRE_MAGIC, decode_update_leaves, tree_from_records,
)
from repro.core import FTTQConfig
from repro.core import fttq as F
from repro.core.compression import wire_nbytes
from repro.core.tfedavg import client_update_payload, server_requantize
from repro.core.ternary import TernaryTensor, encode_ternary
from repro.kernels.pack2bit import pack2bit as pallas_pack2bit
from repro.kernels.pack2bit import pad_to_packable, unpack_padded
from repro.models.paper_models import init_mlp_mnist

CFG = FTTQConfig()


def _leaves(tree):
    return jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, TernaryTensor)
    )[0]


def assert_trees_bitexact(a, b):
    la, lb = _leaves(a), _leaves(b)
    assert len(la) == len(lb)
    for (pa, xa), (pb, xb) in zip(la, lb):
        assert pa == pb
        if isinstance(xa, TernaryTensor):
            assert isinstance(xb, TernaryTensor)
            assert xa.shape == xb.shape and xa.dtype == xb.dtype
            np.testing.assert_array_equal(np.asarray(xa.packed), np.asarray(xb.packed))
            np.testing.assert_array_equal(np.asarray(xa.w_q), np.asarray(xb.w_q))
        else:
            assert xa.dtype == xb.dtype
            np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))


# --------------------------------------------------------------------------
# Round trips.
# --------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "float16", "int32"])
def test_raw_roundtrip_dtypes(dtype):
    x = jnp.arange(30).reshape(5, 6).astype(jnp.dtype(dtype))
    tree = {"layer": {"w": x, "b": jnp.zeros((3,), jnp.dtype(dtype))}}
    assert_trees_bitexact(tree, decode_update(encode_update(tree)))


@pytest.mark.parametrize("shape", [(), (1,), (7,), (5, 3), (3, 5, 7), (2, 3, 4, 5)])
def test_raw_roundtrip_shapes(shape):
    rng = np.random.default_rng(0)
    tree = {"x": jnp.asarray(rng.normal(size=shape).astype(np.float32))}
    assert_trees_bitexact(tree, decode_update(encode_update(tree)))


@pytest.mark.parametrize("n", [1, 3, 5, 17, 4096, 999])
def test_ternary_roundtrip_non_multiple_of_4(n):
    rng = np.random.default_rng(n)
    i_t = jnp.asarray(rng.integers(-1, 2, size=(n,)).astype(np.int8))
    t = encode_ternary(i_t, jnp.float32(0.37))
    tree = {"w": t}
    back = decode_update(encode_update(tree))["w"]
    np.testing.assert_array_equal(np.asarray(back.ternary()), np.asarray(i_t))
    assert float(back.w_q) == pytest.approx(0.37)


def test_model_payload_roundtrip_bitexact():
    """A full client payload (TernaryTensor weights + fp32 biases)."""
    params = init_mlp_mnist(jax.random.PRNGKey(0))
    wq = F.init_wq_tree(params, CFG)
    payload = client_update_payload(params, wq, CFG)
    assert_trees_bitexact(payload, decode_update(encode_update(payload)))


def test_stacked_scan_leaf_roundtrip():
    """≥3-D stacked scan weights with per-layer w_q scales."""
    params = {"scan": {"w": jax.random.normal(jax.random.PRNGKey(1), (4, 9, 13))}}
    wq = F.init_wq_tree(params, CFG)
    assert wq["scan"]["w"].shape == (4, 1, 1)
    payload = client_update_payload(params, wq, CFG)
    t = payload["scan"]["w"]
    assert isinstance(t, TernaryTensor) and t.shape == (4, 9, 13)
    back = decode_update(encode_update(payload))["scan"]["w"]
    np.testing.assert_array_equal(np.asarray(t.ternary()), np.asarray(back.ternary()))
    np.testing.assert_array_equal(np.asarray(t.w_q), np.asarray(back.w_q))
    assert np.asarray(back.w_q).shape == (4, 1, 1)


def test_list_and_bare_leaf_roundtrip():
    tree = [jnp.arange(4), {"a": jnp.ones((2, 2))}, [jnp.zeros(3), jnp.ones(1)]]
    back = decode_update(encode_update(tree))
    assert isinstance(back, list) and isinstance(back[2], list)
    np.testing.assert_array_equal(np.asarray(back[2][1]), np.ones(1))
    bare = decode_update(encode_update(jnp.arange(9.0)))
    np.testing.assert_array_equal(np.asarray(bare), np.arange(9.0))


def test_int_dict_keys_roundtrip():
    """Int-keyed dicts (e.g. per-layer dicts keyed by index) keep their key
    type and are NOT confused with list indices."""
    tree = {0: jnp.arange(3.0), 1: {"w": jnp.ones((2, 2))}}
    back = decode_update(encode_update(tree))
    assert isinstance(back, dict)
    assert set(back.keys()) == {0, 1}
    np.testing.assert_array_equal(np.asarray(back[1]["w"]), np.ones((2, 2)))
    # a pure int-keyed dict stays a dict, while a list stays a list
    d = decode_update(encode_update({0: jnp.ones(1), 1: jnp.zeros(1)}))
    assert isinstance(d, dict) and set(d.keys()) == {0, 1}
    l = decode_update(encode_update([jnp.ones(1), jnp.zeros(1)]))
    assert isinstance(l, list) and len(l) == 2


def test_tensor_to_bytes_from_bytes():
    i_t = jnp.asarray(np.random.default_rng(3).integers(-1, 2, (11, 5)), jnp.int8)
    t = encode_ternary(i_t, jnp.float32(1.5))
    t2 = TernaryTensor.from_bytes(t.to_bytes())
    np.testing.assert_array_equal(np.asarray(t.ternary()), np.asarray(t2.ternary()))
    assert t2.shape == (11, 5) and t2.dtype == "float32"


# --------------------------------------------------------------------------
# Integrity.
# --------------------------------------------------------------------------


def test_crc_detects_corruption():
    blob = encode_update({"w": jnp.arange(64.0)})
    for offset in (_HEADER.size + 1, len(blob) // 2, len(blob) - 1):
        bad = bytearray(blob)
        bad[offset] ^= 0xFF
        with pytest.raises(WireError, match="CRC32"):
            decode_update(bytes(bad))


def test_truncation_and_magic_and_version_rejected():
    blob = encode_update({"w": jnp.arange(16.0)})
    with pytest.raises(WireError):
        decode_update(blob[: len(blob) - 3])
    with pytest.raises(WireError, match="magic"):
        decode_update(b"XXXX" + blob[4:])
    # an unsupported version (keep everything else): header-level reject
    magic, ver, flags, n, crc, blen = _HEADER.unpack_from(blob)
    bad = _HEADER.pack(WIRE_MAGIC, 99, flags, n, crc, blen) + blob[_HEADER.size:]
    with pytest.raises(WireError, match="version"):
        decode_update(bad)
    with pytest.raises(WireError):
        decode_update(b"")


# --------------------------------------------------------------------------
# In-place decode: what aliases the caller's buffer, and what is copied.
# --------------------------------------------------------------------------


def _leaf_arrays(pairs):
    out = []
    for _, leaf in pairs:
        out += [leaf.packed, leaf.w_q] if isinstance(leaf, TernaryTensor) else [leaf]
    return out


@pytest.mark.parametrize("zero_copy", [True, False])
@pytest.mark.parametrize("kind", ["bytes", "bytearray"])
def test_decode_aliases_only_an_immutable_blob(kind, zero_copy, tmp_path):
    """Zero-copy leaves of a ``bytes`` blob are views of it and nothing is
    copied (``wire.copied_bytes`` 0); every other decode owns its memory, so
    refilling a ``bytearray`` leaves decoded leaves as they were; a flipped
    bit in a record body is refused either way."""
    rng = np.random.default_rng(5)
    codes = jnp.asarray(rng.integers(-1, 2, (64, 33)), jnp.int8)
    blob = encode_update({
        "w": encode_ternary(codes, jnp.float32(0.25)),
        "b": jnp.asarray(rng.standard_normal(257), jnp.float32),
    })
    buf = blob if kind == "bytes" else bytearray(blob)
    obs.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        pairs = decode_update_leaves(buf, zero_copy=zero_copy)
        copied = obs.records()["counters"].get("wire.copied_bytes", 0)
    finally:
        jax.profiler.stop_trace()
        obs.clear()
    arrays = _leaf_arrays(pairs)
    aliased = kind == "bytes" and zero_copy
    u8 = np.frombuffer(buf, np.uint8)
    assert [np.shares_memory(np.asarray(a), u8) for a in arrays] == [aliased] * 3
    own = 0 if zero_copy else sum(np.asarray(a).nbytes for a in arrays)
    assert copied == (0 if kind == "bytes" else len(blob)) + own
    before = [np.array(a) for a in arrays]
    if kind == "bytearray":
        buf[:] = bytes(len(buf))
    for a, b in zip(arrays, before):
        np.testing.assert_array_equal(np.asarray(a), b)
    assert_trees_bitexact(tree_from_records(pairs), decode_update(blob))
    bad = bytearray(blob)
    bad[len(blob) - 100] ^= 0x10       # in "w"'s packed codes, the last record
    with pytest.raises(WireError, match="CRC32"):
        decode_update_leaves(bad if kind == "bytearray" else bytes(bad),
                             zero_copy=zero_copy)


# --------------------------------------------------------------------------
# The measured-size contract.
# --------------------------------------------------------------------------


def test_serialized_size_matches_content_within_framing():
    """len(encode_update) == raw content bytes + bounded per-record framing."""
    params = init_mlp_mnist(jax.random.PRNGKey(2))
    wire_tree = server_requantize(params, CFG)
    blob = encode_update(wire_tree)
    assert wire_nbytes(wire_tree) == len(blob) == update_nbytes(wire_tree)

    content = 0
    leaves = _leaves(wire_tree)
    for _, leaf in leaves:
        if isinstance(leaf, TernaryTensor):
            content += int(np.asarray(leaf.packed).nbytes)
            content += int(np.asarray(leaf.w_q).nbytes)
        else:
            content += int(np.asarray(leaf).nbytes)
    overhead = len(blob) - content
    assert 0 < overhead <= _HEADER.size + 96 * len(leaves)


def test_compression_ratio_on_wire():
    """fp32 vs ternary serialized buffers reproduce the ~16× of Table IV
    (slightly under: biases ship fp32 and framing adds bytes)."""
    params = init_mlp_mnist(jax.random.PRNGKey(4))
    fp = update_nbytes(params)
    tern = update_nbytes(server_requantize(params, CFG))
    assert 10 < fp / tern < 16.5


# --------------------------------------------------------------------------
# Pallas codec padding helper.
# --------------------------------------------------------------------------


@pytest.mark.parametrize("n", [1, 5, 129, 1000])
def test_pallas_pad_pack_roundtrip(n):
    rng = np.random.default_rng(n)
    it = jnp.asarray(rng.integers(-1, 2, size=(n,)), jnp.int8)
    tiled, count = pad_to_packable(it, lanes=128)
    assert count == n and tiled.shape[0] % 4 == 0 and tiled.shape[1] == 128
    packed = pallas_pack2bit(tiled, interpret=True)
    out = unpack_padded(packed, count, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(it))
