"""Fused packed fan-in aggregation: kernel-vs-oracle, streaming Aggregator
vs the list-based reference (``server_aggregate``), jit-trace bucketing, and
the C-sharded ``shard_map`` path (subprocess with 8 forced host devices)."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm.wire import encode_update
from repro.core import CodecSpec, FTTQConfig, compress_pytree
from repro.core import fttq as F
from repro.core.tfedavg import (
    TernaryUpdate, client_update_payload, server_aggregate,
)
from repro.fed.aggregator import Aggregator, bucket_for
from repro.kernels.aggregate import (
    LANES, packed_weighted_sum, packed_weighted_sum_ref,
)
from repro.models.paper_models import init_mlp_mnist

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = FTTQConfig()


# --------------------------------------------------------------------------
# Kernel vs numpy oracle.
# --------------------------------------------------------------------------


@pytest.mark.parametrize("c,rows", [(1, 32), (3, 32), (8, 64), (16, 96)])
def test_kernel_matches_oracle(c, rows):
    rng = np.random.default_rng(c * 100 + rows)
    stacked = rng.integers(0, 3, size=(c, rows, LANES), dtype=np.uint8)
    for j in range(1, 4):  # all four bit planes populated, code 3 never used
        stacked |= rng.integers(0, 3, stacked.shape, dtype=np.uint8) << (2 * j)
    coeffs = rng.normal(size=(c,)).astype(np.float32)
    out = np.asarray(packed_weighted_sum(
        jnp.asarray(stacked), jnp.asarray(coeffs), interpret=True
    ))
    np.testing.assert_allclose(
        out, packed_weighted_sum_ref(stacked, coeffs), atol=1e-5
    )


def test_zero_coeff_rows_contribute_nothing():
    """Padding clients carry coeff 0 — even all-ones garbage bytes vanish."""
    rng = np.random.default_rng(0)
    stacked = rng.integers(0, 256, size=(4, 32, LANES), dtype=np.uint8)
    coeffs = np.array([0.5, 0.0, 0.0, 0.25], np.float32)
    zeroed = stacked.copy()
    zeroed[1:3] = 0xFF
    a = np.asarray(packed_weighted_sum(jnp.asarray(stacked), jnp.asarray(coeffs),
                                       interpret=True))
    b = np.asarray(packed_weighted_sum(jnp.asarray(zeroed), jnp.asarray(coeffs),
                                       interpret=True))
    np.testing.assert_array_equal(a, b)


# --------------------------------------------------------------------------
# Streaming Aggregator vs the reference loop.
# --------------------------------------------------------------------------


def _ragged_params(key):
    """Ragged + stacked shapes: n % 4 ≠ 0 weights, per-layer-scale stacks,
    biases, an int counter — every aggregation corner in one tree."""
    k = jax.random.split(key, 5)
    return {
        "enc": {"w": jax.random.normal(k[0], (17, 9)),
                "b": jax.random.normal(k[1], (9,))},
        "stack": {"w": jax.random.normal(k[2], (3, 8, 12))},  # per-layer w_q
        "head": {"w": jax.random.normal(k[3], (12, 5)),
                 "b": jax.random.normal(k[4], (5,))},
        "steps": jnp.asarray(7, jnp.int32),
    }


def _client_payload(key, spec=None):
    params = _ragged_params(key)
    wq = F.init_wq_tree(params, CFG)
    payload = client_update_payload(params, wq, CFG)
    if spec is not None:  # residual codec on the raw leaves
        payload, _ = compress_pytree(payload, spec)
    return payload


def _assert_trees_close(ref, got, atol=1e-6):
    r = jax.tree_util.tree_flatten_with_path(ref)[0]
    g = jax.tree_util.tree_flatten_with_path(got)[0]
    assert len(r) == len(g)
    for (pa, a), (pb, b) in zip(r, g):
        assert jax.tree_util.keystr(pa) == jax.tree_util.keystr(pb)
        assert a.shape == b.shape, (pa, a.shape, b.shape)
        assert a.dtype == b.dtype, (pa, a.dtype, b.dtype)
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=atol, rtol=1e-5, err_msg=str(pa),
        )


@pytest.mark.parametrize("n_clients", [1, 2, 3, 5, 16, 17, 33, 64])
def test_aggregator_matches_reference(n_clients):
    """Streaming output == list-based server_aggregate within 1e-6, across
    ragged leaf shapes, per-layer scales, and chunk/bucket boundaries
    (chunk_c=8: 17 → 8+8+1, 33 → 4 full chunks + 1, 64 → 8 full)."""
    blobs, updates = [], []
    for c in range(n_clients):
        payload = _client_payload(jax.random.PRNGKey(c % 8))
        blobs.append(encode_update(payload))
        updates.append(TernaryUpdate(payload=payload, n_samples=50 + 13 * c))
    ref = server_aggregate(updates)
    agg = Aggregator(chunk_c=8)
    for b, u in zip(blobs, updates):
        agg.add(b, u.n_samples)
    _assert_trees_close(ref, agg.finalize())


@pytest.mark.parametrize("spec", [
    CodecSpec(kind="ternary", residual="fp16", fttq=CFG),
    CodecSpec(kind="ternary", residual="topk", fttq=CFG, topk_fraction=0.5),
])
def test_aggregator_mixed_codec_leaves(spec):
    """Ternary weights take the fused kernel; downcast/top-k residual leaves
    stream through the codec-registry fallback — one pass, same mean."""
    blobs, updates = [], []
    for c in range(6):
        payload = _client_payload(jax.random.PRNGKey(10 + c), spec)
        blobs.append(encode_update(payload))
        updates.append(TernaryUpdate(payload=payload, n_samples=30 + 7 * c))
    ref = server_aggregate(updates)
    agg = Aggregator(chunk_c=4)
    for b, u in zip(blobs, updates):
        agg.add(b, u.n_samples)
    # fp16/topk residuals decode identically on both paths
    _assert_trees_close(ref, agg.finalize(), atol=2e-6)


def test_aggregator_weight_scale_invariance():
    """The mean is invariant to a global rescale of the |D_k| weights."""
    blobs = [encode_update(_client_payload(jax.random.PRNGKey(c)))
             for c in range(4)]
    outs = []
    for scale in (1.0, 1000.0):
        agg = Aggregator(chunk_c=2)
        for i, b in enumerate(blobs):
            agg.add(b, weight=(i + 1) * scale)
        outs.append(agg.finalize())
    _assert_trees_close(outs[0], outs[1], atol=1e-5)


def test_aggregator_refilled_bytearray_matches_bytes():
    """A caller that reuses one ``bytearray`` for every upload, refilling it
    with the next client's bytes after each ``add``, folds exactly what the
    unmutated ``bytes`` fold: nothing pending aliases the reused buffer."""
    blobs = [encode_update(_client_payload(jax.random.PRNGKey(c)))
             for c in range(3)]
    assert len({len(b) for b in blobs}) == 1
    ref, agg = Aggregator(chunk_c=8), Aggregator(chunk_c=8)
    buf = bytearray(len(blobs[0]))
    for i, b in enumerate(blobs):
        ref.add(b, weight=10 + i)
        buf[:] = b
        agg.add(buf, weight=10 + i)
    buf[:] = blobs[0]       # the next round's first upload, before finalize
    _assert_trees_close(ref.finalize(), agg.finalize(), atol=0.0)


def test_aggregator_single_client_is_dequant():
    payload = _client_payload(jax.random.PRNGKey(99))
    agg = Aggregator(chunk_c=16)
    agg.add(encode_update(payload), 42)
    ref = server_aggregate([TernaryUpdate(payload=payload, n_samples=42)])
    _assert_trees_close(ref, agg.finalize())


def test_aggregator_guards():
    agg = Aggregator(chunk_c=4)
    with pytest.raises(ValueError, match="no client updates"):
        agg.finalize()
    blob = encode_update(_client_payload(jax.random.PRNGKey(0)))
    with pytest.raises(ValueError, match="weight must be"):
        agg.add(blob, -1)
    agg.add(blob, 1)
    other = encode_update({"different": jnp.ones((4, 4))})
    with pytest.raises(ValueError, match="structure changed"):
        agg.add(other, 1)
    # an all-zero-weight buffer cannot normalize
    empty = Aggregator(chunk_c=4)
    empty.add(blob, 0)
    with pytest.raises(ValueError, match="total client weight"):
        empty.finalize()


def test_aggregator_zero_weight_client_rides_along():
    """An empty data shard (|D_k| = 0) contributes nothing, exactly like
    the reference's weight-0 entry — the round must not abort."""
    payloads = [_client_payload(jax.random.PRNGKey(c)) for c in range(3)]
    updates = [TernaryUpdate(payload=p, n_samples=w)
               for p, w in zip(payloads, (10, 0, 30))]
    ref = server_aggregate(updates)
    agg = Aggregator(chunk_c=2)
    for p, u in zip(payloads, updates):
        agg.add(encode_update(p), u.n_samples)
    _assert_trees_close(ref, agg.finalize())


def test_bucket_cap_non_power_of_two_chunk():
    assert bucket_for(10, 12) == 12     # cap holds for non-pow2 chunk_c
    assert bucket_for(13, 12) == 12
    assert bucket_for(7, 12) == 8


def test_duplicate_record_paths_rejected():
    """A CRC-valid blob repeating one record would double-count in an
    accumulator (decode_update last-wins it) — the aggregator refuses."""
    import struct
    import zlib

    from repro.comm.wire import _HEADER, WireError

    blob = encode_update({"w": jnp.ones((4,))})
    body = blob[_HEADER.size:]
    dup_body = body + body                     # same path twice
    magic, ver, fl, _, _, _ = _HEADER.unpack_from(blob)
    dup = _HEADER.pack(magic, ver, fl, 2, zlib.crc32(dup_body),
                       len(dup_body)) + dup_body
    agg = Aggregator(chunk_c=4)
    with pytest.raises(WireError, match="duplicate record paths"):
        agg.add(dup, 1)


def test_peak_memory_independent_of_client_count():
    """Chunked streaming: the stacked-buffer high-water mark is a function
    of chunk_c, not of how many clients flow through."""
    peaks = {}
    for n in (8, 32):
        agg = Aggregator(chunk_c=8)
        for c in range(n):
            agg.add(encode_update(_client_payload(jax.random.PRNGKey(c % 4))),
                    10 + c)
        agg.finalize()
        peaks[n] = agg.peak_intermediate_bytes
    assert peaks[8] == peaks[32] > 0


# --------------------------------------------------------------------------
# Trace bucketing: varying client counts must not retrace.
# --------------------------------------------------------------------------


def test_bucket_function():
    assert [bucket_for(c, 16) for c in (1, 2, 3, 5, 8, 9, 15, 16, 40)] == \
        [1, 2, 4, 8, 8, 16, 16, 16, 16]


def test_varying_client_count_no_new_traces():
    """Rounds with client counts all over 1..12 compile only the bucket set:
    after one warm round per bucket, further variation adds zero traces."""
    from repro.parallel.fanin import fanin_trace_count

    mlp_blobs = [encode_update(_client_payload(jax.random.PRNGKey(c)))
                 for c in range(4)]

    def round_with(n):
        agg = Aggregator(chunk_c=4)
        for i in range(n):
            agg.add(mlp_blobs[i % 4], 10 + i)
        agg.finalize()

    for n in (1, 2, 3, 4):   # warm every bucket (1, 2, 4, 4)
        round_with(n)
    before = fanin_trace_count()
    for n in (5, 7, 9, 11, 12, 3, 2, 10):   # new counts, same buckets
        round_with(n)
    assert fanin_trace_count() == before


# --------------------------------------------------------------------------
# Sharded fan-in (shard_map over the client axis).
# --------------------------------------------------------------------------


def test_sharded_fanin_matches_unsharded():
    """8 forced host devices: C-sharded psum fan-in == single-device kernel
    (and the Aggregator produces the reference mean on a mesh)."""
    code = """
    import numpy as np, jax, jax.numpy as jnp
    from repro.parallel.fanin import fanin_weighted_sum
    from repro.kernels.aggregate import packed_weighted_sum_ref
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((8,), ("data",))
    rng = np.random.default_rng(0)
    st = rng.integers(0, 3, size=(16, 32, 128), dtype=np.uint8)
    for j in range(1, 4):
        st |= rng.integers(0, 3, st.shape, dtype=np.uint8) << (2 * j)
    co = rng.normal(size=(16,)).astype(np.float32)
    ref = packed_weighted_sum_ref(st, co)
    out = np.asarray(fanin_weighted_sum(st, co, mesh=mesh))
    np.testing.assert_allclose(out, ref, atol=1e-4)
    # C not divisible by the axis → graceful single-device fallback
    out5 = np.asarray(fanin_weighted_sum(st[:5], co[:5], mesh=mesh))
    np.testing.assert_allclose(out5, packed_weighted_sum_ref(st[:5], co[:5]),
                               atol=1e-4)
    print("FANIN_OK")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=560,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "FANIN_OK" in out.stdout
