"""Zero-copy serve tests: wire bytes → (K//4, N) kernel layout with no
unpacked-int8 / dense-fp32 weight materialization, and packed-kernel logits
matching the dequantized reference path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import decode_update, encode_update
from repro.core import CodecSpec, FTTQConfig
from repro.core import compression as comp
from repro.core.ternary import encode_ternary
from repro.kernels.repack import (
    PackedTernary,
    packed_matmul,
    packed_params_from_wire,
    repack_to_kernel_layout,
)


# --------------------------------------------------------------------------
# Repack correctness, aligned fast path + unaligned fallback.
# --------------------------------------------------------------------------


@pytest.mark.parametrize("k,n", [(64, 48), (32, 16), (128, 128),
                                 (100, 26), (10, 6), (7, 5)])
def test_repack_matches_kernel_reference_layout(k, n):
    """repack(wire bytes) must equal pack2bit_ref of the unpacked codes —
    the exact layout ternary_matmul consumes."""
    from repro.kernels import ref

    rng = np.random.default_rng(k * 1000 + n)
    it = jnp.asarray(rng.integers(-1, 2, (k, n)), jnp.int8)
    t = encode_ternary(it, jnp.float32(0.4))
    p = repack_to_kernel_layout(t)
    k_pad = (k + 3) // 4 * 4
    assert p.packed.shape == (k_pad // 4, n)
    assert p.k == k
    it_pad = jnp.concatenate([it, jnp.zeros((k_pad - k, n), jnp.int8)]) \
        if k_pad != k else it
    np.testing.assert_array_equal(
        np.asarray(p.packed), np.asarray(ref.pack2bit_ref(it_pad)))


@pytest.mark.parametrize("k,n", [(64, 48), (100, 26), (10, 6)])
def test_packed_matmul_equals_dequantized(k, n):
    rng = np.random.default_rng(n)
    it = jnp.asarray(rng.integers(-1, 2, (k, n)), jnp.int8)
    t = encode_ternary(it, jnp.float32(0.37))
    p = repack_to_kernel_layout(t)
    x = jax.random.normal(jax.random.PRNGKey(1), (5, k))
    y = packed_matmul(x, p)
    y_ref = x @ t.dequantize()
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)


def test_repack_stacked_scan_leaf_per_layer_scales():
    rng = np.random.default_rng(9)
    it = jnp.asarray(rng.integers(-1, 2, (3, 32, 16)), jnp.int8)
    wq = jnp.asarray([0.2, 0.3, 0.4], jnp.float32).reshape(3, 1, 1)
    p = repack_to_kernel_layout(encode_ternary(it, wq))
    assert p.packed.shape == (3, 8, 16) and p.w_q.shape == (3, 1, 1)
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 32))
    for layer in range(3):
        per_layer = jax.tree_util.tree_map(lambda a: a[layer], p)
        y = packed_matmul(x, per_layer)
        y_ref = x @ (it[layer].astype(jnp.float32) * wq[layer, 0, 0])
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4)


def test_aligned_repack_never_materializes_unpacked_codes():
    """The aligned fast path is pure byte-plane arithmetic: its transient
    buffers stay at packed size (k·n/4), not unpacked int8 (k·n)."""
    from repro.kernels.repack import _repack2d_aligned

    k, n = 256, 256
    rng = np.random.default_rng(0)
    it = jnp.asarray(rng.integers(-1, 2, (k, n)), jnp.int8)
    t = encode_ternary(it, jnp.float32(1.0))
    flat = np.asarray(t.packed)
    out = _repack2d_aligned(flat, k, n)
    assert out.nbytes == k * n // 4  # kernel layout is still 2-bit packed
    # numerical equivalence with the int8 route, without taking it
    from repro.kernels import ref
    np.testing.assert_array_equal(out, np.asarray(ref.pack2bit_ref(it)))


# --------------------------------------------------------------------------
# Wire → packed params → transformer forward (the acceptance check).
# --------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_lm():
    from repro.models.transformer import ModelConfig, init_params

    cfg = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=32,
                      vocab_size=64, n_heads=4, n_kv_heads=2, d_ff=64)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_packed_params_from_wire_leaf_types(tiny_lm):
    cfg, params = tiny_lm
    wire, _ = comp.compress_pytree(
        params, CodecSpec(kind="ternary", residual="fp16", fttq=FTTQConfig()))
    decoded = decode_update(encode_update(wire))
    packed = packed_params_from_wire(decoded)
    leaves = jax.tree_util.tree_leaves(
        packed, is_leaf=lambda x: isinstance(x, PackedTernary))
    kinds = {type(l).__name__ for l in leaves}
    assert "PackedTernary" in kinds            # matmul weights stayed 2-bit
    assert not any(comp.is_wire_leaf(l) for l in leaves
                   if not isinstance(l, PackedTernary))  # rest decoded dense
    n_packed = sum(isinstance(l, PackedTernary) for l in leaves)
    assert n_packed == 7  # wq wk wv wo w_in w_gate w_out (stacked)


def test_packed_serve_logits_match_dequantized_path(tiny_lm):
    """serve --ternary --packed equivalence: full prefill + cached decode
    through kernels.ternary_matmul matches the dense-dequant reference."""
    from repro.launch.serve import ternary_deploy
    from repro.models.transformer import decode_step, forward, init_cache

    cfg, params = tiny_lm
    packed, blob_p, _, _ = ternary_deploy(params, FTTQConfig(), packed=True)
    dense, blob_d, _, _ = ternary_deploy(params, FTTQConfig(), packed=False)
    assert blob_p == blob_d  # same wire artifact feeds both paths

    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab_size)
    lp, _, _ = forward(cfg, packed, toks)
    lr, _, _ = forward(cfg, dense, toks)
    np.testing.assert_allclose(np.asarray(lp), np.asarray(lr),
                               rtol=1e-4, atol=1e-4)

    cache_p, cache_r = init_cache(cfg, 2, 16), init_cache(cfg, 2, 16)
    lp, cache_p, _ = forward(cfg, packed, toks, cache=cache_p, pos=0)
    lr, cache_r, _ = forward(cfg, dense, toks, cache=cache_r, pos=0)
    tok = jnp.argmax(lp[:, -1:], -1).astype(jnp.int32)
    s_p, _ = decode_step(cfg, packed, tok, cache_p, 8)
    s_r, _ = decode_step(cfg, dense, tok, cache_r, 8)
    np.testing.assert_allclose(np.asarray(s_p), np.asarray(s_r),
                               rtol=1e-4, atol=1e-4)


def test_packed_hbm_bytes_are_2bit(tiny_lm):
    """The served weight leaves occupy ~1/16 of the fp32 footprint in
    memory — the deploy path holds packed bytes, not dense copies."""
    cfg, params = tiny_lm
    from repro.launch.serve import ternary_deploy

    packed, _, _, _ = ternary_deploy(params, FTTQConfig(), packed=True)

    def leaf_bytes(tree):
        total = 0
        for l in jax.tree_util.tree_leaves(
                tree, is_leaf=lambda x: isinstance(x, PackedTernary)):
            if isinstance(l, PackedTernary):
                total += int(l.packed.size) + int(np.asarray(l.w_q).nbytes)
            else:
                total += int(np.asarray(l).nbytes)
        return total

    quantizable = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        from repro.core import fttq
        if fttq.is_quantizable(path, leaf, FTTQConfig()):
            quantizable += leaf.nbytes
    # served bytes ≈ fp32_total − quantizable·(1 − 1/16)
    fp32_total = sum(l.nbytes for l in jax.tree_util.tree_leaves(params))
    expected = fp32_total - quantizable * (1 - 1 / 16)
    assert leaf_bytes(packed) < expected * 1.05


def test_packed_matmul_bad_k_raises():
    it = jnp.asarray(np.random.default_rng(0).integers(-1, 2, (16, 8)), jnp.int8)
    p = repack_to_kernel_layout(encode_ternary(it, jnp.float32(1.0)))
    with pytest.raises(ValueError, match="contraction dim"):
        packed_matmul(jnp.ones((2, 12)), p)
    it3 = jnp.asarray(np.random.default_rng(1).integers(-1, 2, (2, 16, 8)), jnp.int8)
    p3 = repack_to_kernel_layout(encode_ternary(it3, jnp.float32(1.0)))
    with pytest.raises(ValueError, match="scan over the leading axis"):
        packed_matmul(jnp.ones((2, 16)), p3)


def test_packed_logits_gap_against_reference_from_same_blob(tiny_lm):
    """serve's correctness receipt rebuilds the dense reference from the
    wire blob alone (the fp32 tree may already be gone) and reports the
    packed-vs-dequant gap next to the reference logits' scale."""
    from repro.launch.serve import packed_logits_gap, ternary_deploy

    cfg, params = tiny_lm
    served, blob, _, _ = ternary_deploy(params, FTTQConfig(), packed=True)
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 8), 0, cfg.vocab_size)
    gap, scale = packed_logits_gap(cfg, served, blob, toks)
    assert scale > 0
    assert gap <= 1e-4 * max(scale, 1.0)
